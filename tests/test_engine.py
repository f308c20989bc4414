"""Compiled multi-round engine: scan/host parity, participation, strategy
registry, device-side data, checkpoint resume.

The load-bearing invariant: ``run_chunk`` over k rounds is BIT-identical to
k sequential ``run_round`` calls (same seed) — per-round and chunked
execution are the same compiled computation, for every strategy, under both
the reference and interpret kernel tiers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core.aggregation import (REGISTRY, STRATEGIES, Strategy,
                                    get_strategy, negate_flag, strategy_flags)
from repro.core.federated import FederatedTrainer, participation_weights
from repro.data.synthetic import DeviceFederatedData, FederatedDataset
from repro.models.api import build_model


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="eng", family="dense", num_layers=2, d_model=64,
                      num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                      vocab_size=64)
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.key(0))


def make_trainer(model, base, *, strategy="fedsa", n=4, participation=1.0,
                 chunk_rounds=0, data_mode="host", seed=0, rank=4,
                 local_steps=2):
    ds = FederatedDataset(64, n, seq_len=32, batch_per_client=2, seed=seed)
    return FederatedTrainer(
        model, ds, lora_cfg=LoRAConfig(rank=rank),
        fed_cfg=FederatedConfig(num_clients=n, local_steps=local_steps,
                                aggregation=strategy,
                                participation=participation),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), seed=seed,
        base_params=base, chunk_rounds=chunk_rounds, data_mode=data_mode)


def assert_trees_bitequal(t1, t2):
    for x, y in zip(jax.tree.leaves(t1), jax.tree.leaves(t2)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def assert_state_bitequal(tr_a, tr_b):
    assert_trees_bitequal(tr_a.lora, tr_b.lora)
    assert_trees_bitequal(tr_a.opt_state, tr_b.opt_state)


# --------------------------------------------------------- chunk == rounds

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_run_chunk_bit_identical_to_sequential_rounds(tiny, strategy):
    """Satellite: run_chunk(k rounds) == k x run_round, bit-exact, for
    every registered strategy (k=5 is odd so rolora ends mid-alternation)."""
    cfg, model, base = tiny
    tr_seq = make_trainer(model, base, strategy=strategy, chunk_rounds=1)
    for _ in range(5):
        tr_seq.run_round()
    tr_chunk = make_trainer(model, base, strategy=strategy, chunk_rounds=5)
    tr_chunk.run(5)
    assert_state_bitequal(tr_seq, tr_chunk)
    np.testing.assert_array_equal([h["loss"] for h in tr_seq.history],
                                  [h["loss"] for h in tr_chunk.history])


def test_chunk_boundaries_do_not_matter(tiny):
    """6 rounds as 1+2+3 == one chunk of 6 (rolora: boundaries land on both
    parities, so the round-offset carry is exercised)."""
    cfg, model, base = tiny
    tr_a = make_trainer(model, base, strategy="rolora")
    tr_a.chunk_rounds = 1
    tr_a.run(1)
    tr_a.chunk_rounds = 2
    tr_a.run(2)
    tr_a.chunk_rounds = 3
    tr_a.run(3)
    tr_b = make_trainer(model, base, strategy="rolora", chunk_rounds=6)
    tr_b.run(6)
    assert tr_a.round_idx == tr_b.round_idx == 6
    assert_state_bitequal(tr_a, tr_b)


def test_device_data_mode_chunk_parity_and_training(tiny):
    """On-device batch synthesis inside the scan: same bit-exact chunk
    parity (randomness flows from the carried key), and the loss is finite
    and decreasing-ish over a short run."""
    cfg, model, base = tiny
    tr_seq = make_trainer(model, base, data_mode="device", chunk_rounds=1)
    for _ in range(4):
        tr_seq.run_round()
    tr_chunk = make_trainer(model, base, data_mode="device", chunk_rounds=4)
    tr_chunk.run(4)
    assert_state_bitequal(tr_seq, tr_chunk)
    assert all(np.isfinite(h["loss"]) for h in tr_chunk.history)


def test_device_sampler_shape_and_determinism(tiny):
    ds = FederatedDataset(64, 3, seq_len=16, batch_per_client=2, seed=0)
    dev = DeviceFederatedData.from_host(ds)
    toks = dev.sample_round(jax.random.key(7), 2)
    assert toks.shape == (3, 2, 2, 16) and toks.dtype == jnp.int32
    assert int(toks.min()) >= 0 and int(toks.max()) < 64
    toks2 = dev.sample_round(jax.random.key(7), 2)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(toks2))
    assert not np.array_equal(
        np.asarray(toks), np.asarray(dev.sample_round(jax.random.key(8), 2)))


# ------------------------------------------------------ partial participation

def test_partial_participation_chunk_parity(tiny):
    """weights path: scan engine and per-round engine sample the SAME
    clients (randomness from the carried key, not a host RNG) and produce
    bit-identical state."""
    cfg, model, base = tiny
    tr_seq = make_trainer(model, base, participation=0.5, chunk_rounds=1)
    for _ in range(5):
        tr_seq.run_round()
    tr_chunk = make_trainer(model, base, participation=0.5, chunk_rounds=5)
    tr_chunk.run(5)
    assert_state_bitequal(tr_seq, tr_chunk)


def test_partial_participation_nonsampled_receive_aggregate(tiny):
    """Non-sampled clients keep their local state (B, opt) but receive the
    aggregated A — checked per-round via the optimizer step counters."""
    cfg, model, base = tiny
    tr = make_trainer(model, base, participation=0.5, chunk_rounds=1)
    prev_t = np.asarray(tr.opt_state["t"]).copy()
    for _ in range(4):
        tr.run_round()
        t = np.asarray(tr.opt_state["t"])
        stepped = t > prev_t
        # exactly k=2 of 4 clients train each round
        assert int(stepped.sum()) == 2
        # aggregated A identical across ALL clients (incl. non-sampled)
        a = np.asarray(tr.lora["stack"]["repeat"]["p0"]["attn"]["q"]["a"])
        for i in range(1, 4):
            np.testing.assert_allclose(a[0], a[i], rtol=1e-6, atol=1e-7)
        prev_t = t


def test_participation_weights_exact_count():
    w = participation_weights(jax.random.key(0), 10, 3)
    assert w.shape == (10,) and float(w.sum()) == 3.0
    assert set(np.unique(np.asarray(w))) <= {0.0, 1.0}


def _perturb_b(tr):
    """Give B a deterministic nonzero value: at the standard B=0 init, A's
    gradient is identically zero (dL/dA = B^T dY x), so an A-round would be
    a no-op and the alternation unobservable."""
    from repro.core.aggregation import _map_ab
    counter = [0]

    def pb(b):
        counter[0] += 1
        k = jax.random.fold_in(jax.random.key(99), counter[0])
        return 0.02 * jax.random.normal(k, b.shape, b.dtype)

    tr.lora = _map_ab(tr.lora, lambda a: a, pb)


def test_rolora_alternation_equivalence(tiny):
    """rolora round-alternation is identical between host-loop and scan
    engines: even rounds touch only A, odd rounds only B, across a chunk
    boundary that splits the parity."""
    cfg, model, base = tiny
    tr = make_trainer(model, base, strategy="rolora", chunk_rounds=1)
    _perturb_b(tr)
    q = lambda t: t.lora["stack"]["repeat"]["p0"]["attn"]["q"]
    a0, b0 = (np.asarray(q(tr)["a"]).copy(), np.asarray(q(tr)["b"]).copy())
    tr.run_round()                                   # round 0: A trains
    a1, b1 = np.asarray(q(tr)["a"]), np.asarray(q(tr)["b"])
    assert not np.array_equal(a0, a1)
    np.testing.assert_array_equal(b0, b1)
    tr.run_round()                                   # round 1: B trains
    a2, b2 = np.asarray(q(tr)["a"]), np.asarray(q(tr)["b"])
    np.testing.assert_array_equal(a1, a2)
    assert not np.array_equal(b1, b2)
    # the same two rounds as one scanned chunk
    tr2 = make_trainer(model, base, strategy="rolora", chunk_rounds=2)
    _perturb_b(tr2)
    tr2.run(2)
    assert_state_bitequal(tr, tr2)


# ----------------------------------------------------------------- registry

def test_registry_covers_and_roundtrips():
    assert set(REGISTRY) == set(STRATEGIES)
    for name in STRATEGIES:
        s = get_strategy(name)
        assert isinstance(s, Strategy) and s.name == name
        assert get_strategy(s) is s
    with pytest.raises(ValueError, match="unknown strategy"):
        get_strategy("nope")


def test_negate_flag_uniform():
    assert negate_flag(True) is False and negate_flag(False) is True
    traced = jax.jit(lambda r: negate_flag(r % 2 == 0))(jnp.asarray(1))
    assert bool(traced) is True


def test_strategy_flags_backcompat_matches_registry():
    for name in ("fedit", "ffa", "fedsa", "rolora"):
        s = get_strategy(name)
        for ridx in (0, 1):
            assert strategy_flags(name, ridx) == (s.train_flags(ridx),
                                                  s.agg_flags(ridx))


def test_strategy_flags_rejects_non_flag_strategies():
    """flora's stacking aggregate is not expressible as agg flags; the
    back-compat shim must refuse rather than describe plain means."""
    with pytest.raises(ValueError, match="not flag-expressible"):
        strategy_flags("flora", 0)


def test_upload_bytes_strategy_method():
    lora = {"x": {"q": {"a": jnp.zeros((2, 4, 8)), "b": jnp.zeros((2, 8, 4))}}}
    per = 4 * 8 * 4                       # one matrix, f32
    assert get_strategy("fedsa").upload_bytes(lora) == per
    assert get_strategy("fedit").upload_bytes(lora) == 2 * per
    assert get_strategy("flora").upload_bytes(lora) == 2 * per   # stacks A+B
    assert get_strategy("rolora").upload_bytes(lora, 0) == per
    assert get_strategy("rolora").upload_bytes(lora, 1) == per


def test_flora_stacking_exact_mean_product():
    """When the mean update fits in rank r, the redistributed factorization
    reproduces mean_i(B_i A_i) exactly and is identical across clients."""
    k1, k2 = jax.random.split(jax.random.key(0))
    n, r, d = 2, 4, 8
    a = jax.random.normal(k1, (n, r, d))
    b = jnp.zeros((n, d, r)).at[:, :, :1].set(
        jax.random.normal(k2, (n, d, 1)))            # rank-1 per client
    lora = {"x": {"q": {"a": a, "b": b}}}
    out = get_strategy("flora").aggregate(lora, 0)
    oa, ob = out["x"]["q"]["a"], out["x"]["q"]["b"]
    np.testing.assert_allclose(np.asarray(oa[0]), np.asarray(oa[1]))
    want = np.mean([np.asarray(b[i] @ a[i]) for i in range(n)], axis=0)
    got = np.asarray(ob[0] @ oa[0])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_flora_trains(tiny):
    cfg, model, base = tiny
    tr = make_trainer(model, base, strategy="flora", chunk_rounds=3)
    tr.run(3)
    assert all(np.isfinite(h["loss"]) for h in tr.history)
    # redistribution synchronizes both matrices across clients
    q = tr.lora["stack"]["repeat"]["p0"]["attn"]["q"]
    np.testing.assert_allclose(np.asarray(q["a"][0]), np.asarray(q["a"][1]))
    np.testing.assert_allclose(np.asarray(q["b"][0]), np.asarray(q["b"][1]))


# ----------------------------------------------------------- interpret tier

def test_engine_parity_interpret_tier():
    """The chunked scan is bit-identical to sequential rounds on the fused
    kernel path too (Pallas interpreter on CPU)."""
    from repro.kernels import dispatch
    cfg = ModelConfig(name="eng-pl", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
                      vocab_size=64, use_pallas=True)
    model = build_model(cfg)
    base = model.init(jax.random.key(0))
    dispatch.force_mode("interpret")
    try:
        def mk(chunk):
            ds = FederatedDataset(64, 2, seq_len=8, batch_per_client=1,
                                  seed=0)
            return FederatedTrainer(
                model, ds, lora_cfg=LoRAConfig(rank=4),
                fed_cfg=FederatedConfig(num_clients=2, local_steps=1),
                opt_cfg=OptimizerConfig(name="sgd", lr=0.05),
                base_params=base, chunk_rounds=chunk)
        tr_seq = mk(1)
        tr_seq.run_round()
        tr_seq.run_round()
        tr_chunk = mk(2)
        tr_chunk.run(2)
    finally:
        dispatch.force_mode(None)
    assert_state_bitequal(tr_seq, tr_chunk)


# --------------------------------------------------------------- checkpoint

def test_checkpoint_resume_bit_exact(tiny, tmp_path):
    """Satellite: checkpoints carry the PRNG key + round index (+ host data
    stream state), so save-at-k / restore / continue equals an uninterrupted
    run — including participation sampling randomness."""
    cfg, model, base = tiny
    path = str(tmp_path / "resume.npz")

    tr_full = make_trainer(model, base, participation=0.5, chunk_rounds=2)
    tr_full.run(6)

    tr_half = make_trainer(model, base, participation=0.5, chunk_rounds=2)
    tr_half.run(2)
    tr_half.save(path)

    tr_res = make_trainer(model, base, participation=0.5, chunk_rounds=2)
    tr_res.restore(path)
    assert tr_res.round_idx == 2
    tr_res.run(4)
    assert tr_res.round_idx == 6
    assert_state_bitequal(tr_full, tr_res)


def test_checkpoint_resume_device_data(tiny, tmp_path):
    cfg, model, base = tiny
    path = str(tmp_path / "resume_dev.npz")
    tr_full = make_trainer(model, base, data_mode="device", chunk_rounds=3)
    tr_full.run(6)
    tr_half = make_trainer(model, base, data_mode="device", chunk_rounds=3)
    tr_half.run(3)
    tr_half.save(path)
    tr_res = make_trainer(model, base, data_mode="device", chunk_rounds=3)
    tr_res.restore(path)
    tr_res.run(3)
    assert_state_bitequal(tr_full, tr_res)


# -------------------------------------------------------------------- mesh

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax, numpy as np
from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core.federated import FederatedTrainer
from repro.data.synthetic import FederatedDataset
from repro.launch.mesh import mesh_from_spec

cfg = ModelConfig(name="m", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=64)
from repro.models.api import build_model
model = build_model(cfg)
base = model.init(jax.random.key(0))

def make(mesh, data_mode):
    ds = FederatedDataset(64, 4, seq_len=32, batch_per_client=2, seed=0)
    return FederatedTrainer(model, ds, lora_cfg=LoRAConfig(rank=8),
        fed_cfg=FederatedConfig(num_clients=4, local_steps=2,
                                aggregation="fedsa"),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), base_params=base,
        chunk_rounds=3, mesh=mesh, data_mode=data_mode)

ref = make(None, "host"); ref.run(3)
mesh = mesh_from_spec("4x2")
tr = make(mesh, "host"); tr.run(3)          # client dim sharded over "data"
ok = all(np.allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=1e-7)
         for x, y in zip(jax.tree.leaves(ref.lora), jax.tree.leaves(tr.lora)))
a_shard = str(jax.tree.leaves(tr.lora)[0].sharding.spec)
dev = make(mesh, "device"); dev.run(3)      # on-device data on the mesh
compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda ev, secs, fun_name="", **kw: compiles.append(fun_name)
    if ev == "/jax/core/compile/backend_compile_duration" else None)
dev.run(3)      # the second chunk takes the first's outputs: no recompile
# no base given: the trainer initializes the base straight into its mesh
# placement (one jitted init; the one-device init is eager, and XLA may
# round a fused scale differently by an ulp)
def fresh(m):
    ds = FederatedDataset(64, 4, seq_len=32, batch_per_client=2, seed=0)
    return FederatedTrainer(model, ds, lora_cfg=LoRAConfig(rank=8),
        fed_cfg=FederatedConfig(num_clients=4), opt_cfg=OptimizerConfig(),
        mesh=m).base
b1, bm = fresh(None), fresh(mesh)
base_ok = all(np.allclose(np.asarray(x), np.asarray(y), rtol=1e-6, atol=0)
              for x, y in zip(jax.tree.leaves(b1), jax.tree.leaves(bm)))
embed_spec = str(bm["embed"].sharding.spec)
print(json.dumps({"match": bool(ok), "a_spec": a_shard,
                  "dev_loss_finite": bool(np.isfinite(
                      dev.history[-1]["loss"])),
                  "chunk_recompiles": compiles.count("jit(run_chunk)"),
                  "base_init_match": bool(base_ok),
                  "embed_spec": embed_spec}))
"""


@pytest.mark.slow
def test_trainer_on_mesh_matches_single_device(tmp_path):
    """The real trainer with mesh=...: client dim sharded over 'data',
    numerics match the 1-device run, device-data mode runs on the mesh.
    Subprocess: jax locks the device count at first init."""
    import json
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["match"], rec
    assert "data" in rec["a_spec"], rec
    assert rec["dev_loss_finite"], rec
    assert rec["chunk_recompiles"] == 0, rec
    assert rec["base_init_match"] and "model" in rec["embed_spec"], rec


def test_engine_history_and_metrics_format(tiny):
    cfg, model, base = tiny
    tr = make_trainer(model, base, chunk_rounds=3)
    hist = tr.run(3)
    assert [h["round"] for h in hist] == [1, 2, 3]
    assert all(isinstance(h["loss"], float) and
               isinstance(h["grad_norm"], float) for h in hist)
