"""repro.analysis: lint rules R1-R7, pragma policy, runtime sanitizers
(RecompileGuard / transfer guard), and the Theorem 4.2 collapse sentinel.

Every lint rule gets a bad fixture (the historical bug class it encodes,
reduced to a few lines) and a good fixture (the idiom that replaced it) —
the rule must flag the former and stay silent on the latter.  The
sanitizer tests SEED the failure (a shape-churning engine, a numpy operand
into a warmed jit) and assert the guard converts it into a loud error.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hostcheck import HostOnlyError, check_adapter_ids, host_only
from repro.analysis.lint import LintConfig, lint_source, report
from repro.analysis.sanitizers import (RecompileError, RecompileGuard,
                                       TransferGuardError, guard_transfers,
                                       no_implicit_transfers)
from repro.analysis.stability_check import (ScalingCollapseError,
                                            assert_stabilized,
                                            predicted_scale, scaling_flatness,
                                            stability_report)
from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core.federated import FederatedTrainer
from repro.core.lora import AdapterBank, LiveAdapterBank, init_adapter_set
from repro.data.synthetic import FederatedDataset
from repro.launch import serve
from repro.models.api import build_model


# --------------------------------------------------------------- lint helpers

def _findings(src, **cfg):
    config = LintConfig(**cfg) if cfg else None
    return lint_source("<fixture>", textwrap.dedent(src), config)


def _active_rules(src, **cfg):
    return sorted({f.rule for f in _findings(src, **cfg) if not f.suppressed})


# ------------------------------------------------------- R1: host nondeterminism

def test_r1_flags_host_time_in_jitted_body():
    assert "R1" in _active_rules("""
        import time
        import jax

        @jax.jit
        def step(x):
            return x * time.time()
        """)


def test_r1_flags_np_random_in_scan_body():
    # indirectly traced: the def is passed to lax.scan, not decorated
    assert "R1" in _active_rules("""
        import jax
        import numpy as np
        from jax import lax

        def body(c, x):
            return c + np.random.randn(), x

        def run(xs):
            return lax.scan(body, 0.0, xs)
        """)


def test_r1_allows_host_time_outside_traces():
    assert _active_rules("""
        import time

        def bench(fn):
            t0 = time.perf_counter()
            fn()
            return time.perf_counter() - t0
        """) == []


# ------------------------------------------------------------- R2: inline jit

def test_r2_flags_jit_in_loop_body():
    assert "R2" in _active_rules("""
        import jax

        def serve_all(xs):
            out = []
            for x in xs:
                out.append(jax.jit(lambda y: y + 1)(x))
            return out
        """)


def test_r2_flags_jit_of_bound_method():
    # model.decode_step is a fresh bound-method object per access: jitting
    # it inline builds a new executable cache on every call (the bug the
    # per-model jit cache, serve._model_jit, exists to prevent)
    assert "R2" in _active_rules("""
        import jax

        def decode(model, tok):
            return jax.jit(model.decode_step)(tok)
        """)


def test_r2_allows_builder_and_module_level_jit():
    assert _active_rules("""
        import jax

        step = jax.jit(lambda y: y + 1)

        def make_step(model):
            return jax.jit(lambda p, t: model.apply(p, t))
        """) == []


# ----------------------------------------------------------- R3: pytree aux

def test_r3_flags_unhashable_aux():
    assert "R3" in _active_rules("""
        import jax

        class Box:
            def tree_flatten(self):
                return (self.x,), [self.meta]
        """)


def test_r3_allows_tuple_aux():
    assert _active_rules("""
        import jax

        class Box:
            def tree_flatten(self):
                return (self.x,), (self.meta,)
        """) == []


# -------------------------------------------------- R4: unguarded host coercion

def test_r4_flags_bare_np_coercion_of_param():
    assert "R4" in _active_rules("""
        import numpy as np
        import jax

        def log_stats(x):
            return float(np.asarray(x).mean())
        """)


def test_r4_allows_host_only_guarded_def():
    assert _active_rules("""
        import numpy as np
        import jax
        from repro.analysis.hostcheck import host_only

        @host_only
        def log_stats(x):
            return float(np.asarray(x).mean())
        """) == []


# -------------------------------------------------- R5: unvalidated id gather

def test_r5_flags_bare_adapter_id_gather():
    assert "R5" in _active_rules("""
        import jax.numpy as jnp

        def gather(bank, ids):
            return bank[ids]
        """)


def test_r5_allows_checked_gather():
    assert _active_rules("""
        from repro.analysis.hostcheck import check_adapter_ids

        def gather(bank, ids):
            check_adapter_ids(ids, bank.shape[0])
            return bank[ids]
        """) == []


# ----------------------------------------------------- R6: Pallas discipline

_PALLAS_HEADER = """
    import jax
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]
"""


def test_r6_flags_vmem_budget_blowout():
    # (4096, 4096) fp32 blocks double-buffered: ~256 MiB >> 16 MiB budget
    assert "R6" in _active_rules(_PALLAS_HEADER + """
        def run(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                in_specs=[pl.BlockSpec((4096, 4096), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((4096, 4096), lambda i: (0, 0)),
            )(x)
        """)


def test_r6_flags_impure_index_map():
    assert "R6" in _active_rules(_PALLAS_HEADER + """
        def pick(i):
            return i

        def run(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (pick(i), 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (i, 0)),
            )(x)
        """)


def test_r6_allows_disciplined_call():
    assert _active_rules(_PALLAS_HEADER + """
        BM = 128

        def run(x):
            return pl.pallas_call(
                kernel,
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                in_specs=[pl.BlockSpec((BM, BM), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((BM, BM), lambda i: (i, 0)),
            )(x)
        """) == []


def test_r6_silent_without_pallas_import():
    # the budget heuristic must not fire on modules that never touch Pallas
    assert _active_rules("""
        def run(x):
            return x.reshape(4096, 4096)
        """) == []


# ----------------------------------------------------- R7: shadowed imports

def test_r7_flags_local_shadow_of_module_level_import():
    assert "R7" in _active_rules("""
        import numpy as np

        def f(x):
            import numpy as np
            return np.sum(x)
        """)


def test_r7_allows_lazy_import_without_module_binding():
    # jax-free modules lazily importing jax inside one function is the
    # repo's deliberate idiom — nothing is shadowed
    assert _active_rules("""
        def f(x):
            import numpy as np
            return np.sum(x)
        """) == []


# ------------------------------------------------------------- pragma policy

_BAD_GATHER = """
    def gather(bank, ids):
        return bank[ids]{pragma}
"""


def test_pragma_with_justification_suppresses():
    src = _BAD_GATHER.format(
        pragma="  # lint: disable=R5 -- ids validated at the host boundary")
    findings = _findings(src)
    assert [f.rule for f in findings] == ["R5"]
    assert findings[0].suppressed
    assert "host boundary" in findings[0].justification
    text, status = report(findings)
    assert status == 0 and "suppressed" in text


def test_pragma_without_justification_is_itself_a_finding():
    findings = _findings(_BAD_GATHER.format(pragma="  # lint: disable=R5"))
    rules = sorted(f.rule for f in findings)
    assert rules == ["PRAGMA", "R5"]          # unexplained pragma: R5 stays live
    assert not any(f.suppressed for f in findings)
    _, status = report(findings)
    assert status == 1


def test_pragma_unknown_rule_is_a_finding():
    findings = _findings(_BAD_GATHER.format(
        pragma="  # lint: disable=R99 -- because"))
    assert "PRAGMA" in {f.rule for f in findings}


def test_report_status_reflects_active_findings():
    _, bad = report(_findings("import time\nimport jax\n\n@jax.jit\n"
                              "def f(x):\n    return x * time.time()\n"))
    _, good = report(_findings("def f(x):\n    return x\n"))
    assert (bad, good) == (1, 0)


def test_lint_runs_clean_on_the_repo_source():
    # the tentpole acceptance bar: src/ lints clean (pragmas justified)
    import os

    from repro.analysis.lint import lint_paths
    root = os.path.join(os.path.dirname(__file__), "..", "src")
    active = [f for f in lint_paths([root]) if not f.suppressed]
    assert active == [], "\n".join(f.render() for f in active)


# ---------------------------------------------------------- RecompileGuard

def test_recompile_guard_watch_mode_detects_cold_shape():
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones((4,)))                       # warm one shape
    guard = RecompileGuard()
    guard.watch("double", f)
    f(jnp.ones((4,)))                       # served shape: fine
    guard.check()
    f(jnp.ones((8,)))                       # cold shape inside guarded region
    with pytest.raises(RecompileError, match="double"):
        guard.check()


def test_recompile_guard_wrap_catches_seeded_recompile():
    """A churn engine: stable outer signature, but an inner static arg
    changes every call so the jit cache grows on a previously-served
    signature — exactly the class of bug wrap mode exists to name."""

    class ChurnEngine:
        def __init__(self):
            self.calls = 0
            self.fn = jax.jit(lambda x, c: x + c, static_argnums=1)

        def __call__(self, x):
            self.calls += 1
            return self.fn(x, self.calls)

    engine = ChurnEngine()
    guard = RecompileGuard()
    step = guard.wrap("churn", engine, cache_probe=engine.fn)
    x = jnp.ones((4,))
    step(x)                                  # first compile: allowed
    with pytest.raises(RecompileError, match="previously-served"):
        step(x)                              # same outer sig, cache grew


def test_recompile_guard_wrap_catches_treedef_churn():
    f = jax.jit(lambda d: sum(jax.tree.leaves(d)))
    guard = RecompileGuard(max_treedef_variants=2)
    step = guard.wrap("aux_churn", f, cache_probe=f)
    x = jnp.ones((4,))
    with pytest.raises(RecompileError, match="distinct treedefs"):
        for i in range(6):                   # per-call dict key = aux churn
            step({f"k{i}": x})


def test_recompile_guard_context_manager_and_stable_engine():
    f = jax.jit(lambda x: x + 1)
    for n in (4, 8):
        f(jnp.ones((n,)))                    # warm every shape up front
    guard = RecompileGuard()
    guard.watch("inc", f)
    with guard:
        for n in (4, 8, 4, 8):
            f(jnp.ones((n,)))                # replays only: exits clean
    assert guard.events == []


# ------------------------------------------------------------ transfer guard

def test_transfer_guard_passes_device_resident_calls():
    f = jax.jit(lambda x: x * 2)
    dev = jnp.arange(8, dtype=jnp.float32)
    f(dev)                                   # warm
    with no_implicit_transfers():
        out = f(dev)
    assert float(out[3]) == 6.0


def test_transfer_guard_catches_seeded_numpy_operand():
    f = jax.jit(lambda x: x * 2)
    f(jnp.arange(8, dtype=jnp.float32))      # warm
    host = np.arange(8, dtype=np.float32)    # un-staged operand
    with pytest.raises(TransferGuardError, match="host boundary"):
        with no_implicit_transfers():
            f(host)


def test_guard_transfers_wrapper():
    f = guard_transfers(jax.jit(lambda x: x + 1))
    assert f.__transfer_guarded__
    dev = jnp.ones((4,))
    f(dev)                                   # warm (staging is the 1st call)
    f(dev)
    with pytest.raises(TransferGuardError):
        f(np.ones((4,), np.float32))


# ----------------------------------------------------------- hostcheck units

def test_host_only_rejects_tracers():
    @host_only
    def to_host(x):
        return np.asarray(x)

    assert to_host(jnp.ones((2,))).shape == (2,)
    with pytest.raises(HostOnlyError, match="to_host"):
        jax.jit(lambda x: to_host(x))(jnp.ones((2,)))


def test_check_adapter_ids_rejects_out_of_range():
    assert check_adapter_ids(np.asarray([0, 1]), 2) is not None
    with pytest.raises(ValueError, match="out of range"):
        check_adapter_ids(np.asarray([0, 2]), 2)
    with pytest.raises(ValueError, match="out of range"):
        check_adapter_ids(np.asarray([-1]), 2)

    def traced(ids):
        check_adapter_ids(ids, 2)            # tracer passthrough: no error
        return ids

    jax.jit(traced)(jnp.asarray([5]))


# -------------------------------------------------- Theorem 4.2 sentinel

def test_sentinel_flags_lora_scaling_collapse_at_high_rank():
    """r=64, N=8: classic LoRA gamma=alpha/r predicts a moment scale of
    (1/r)^2 * r/N = 1/(rN) of alpha^2 — collapse; SFed-LoRA's
    alpha*sqrt(N/r) lands exactly at 1.0."""
    r, n, alpha = 64, 8, 8.0
    flat = [1.0, 1.01, 0.99, 1.0]

    sfed = stability_report(flat, gamma=alpha * np.sqrt(n / r), r=r,
                            n_clients=n, alpha=alpha)
    assert sfed.ok and sfed.verdict == "stabilized"
    assert sfed.predicted == pytest.approx(1.0)

    lora = stability_report(flat, gamma=alpha / r, r=r, n_clients=n,
                            alpha=alpha)
    assert not lora.ok and lora.verdict == "collapse"
    assert lora.predicted == pytest.approx(1.0 / (r * n))
    assert "gamma=alpha*sqrt(N/r)" in str(lora)

    with pytest.raises(ScalingCollapseError, match="collapse"):
        assert_stabilized(flat, gamma=alpha / r, r=r, n_clients=n,
                          alpha=alpha)


def test_sentinel_measured_trend_overrides_good_config():
    r, n, alpha = 16, 4, 8.0
    gamma = alpha * np.sqrt(n / r)
    exploding = [1.0, 4.0, 16.0, 64.0]
    rep = stability_report(exploding, gamma=gamma, r=r, n_clients=n,
                           alpha=alpha)
    assert rep.verdict == "explosion" and not rep.ok


def test_sentinel_reference_ratio_detects_drift():
    r, n, alpha = 16, 4, 8.0
    gamma = alpha * np.sqrt(n / r)
    base = [1.0, 1.0, 1.0]
    # a run whose measured level is 100x the reference while the theorem
    # predicts parity (same gamma/r/N): the aggregation path drifted
    rep = stability_report([100.0, 100.0, 100.0], gamma=gamma, r=r,
                           n_clients=n, alpha=alpha,
                           reference=(base, gamma, r, n))
    assert rep.verdict == "drift" and not rep.ok


def test_scaling_flatness():
    flat, ratio = scaling_flatness({(4, 8): 1.0, (8, 16): 1.2, (16, 64): 0.9})
    assert flat and ratio < 2.0
    flat, _ = scaling_flatness([1.0, 100.0])
    assert not flat


def test_predicted_scale_sfed_invariance():
    for n in (2, 8, 32):
        for r in (4, 16, 64):
            gamma = 8.0 * np.sqrt(n / r)
            assert predicted_scale(gamma, r, n, 8.0) == pytest.approx(1.0)


# -------------------------------------- benchmark trajectory hardening

def test_trajectory_warns_instead_of_silently_skipping(tmp_path, monkeypatch,
                                                       capsys):
    """A historical revision whose BENCH_*.json is unreadable (renamed) or
    malformed must surface as a ``__warning__`` row, not vanish — and the
    readable revisions still print."""
    from benchmarks import run as bench_run

    (tmp_path / "BENCH_t.json").write_text('{"s": {"tok_s": 2.0}}')
    blobs = {
        "aaa:BENCH_t.json": None,                      # git show fails
        "bbb:BENCH_t.json": "{not json",               # malformed snapshot
        "ccc:BENCH_t.json": '{"s": {"tok_s": 2.0}}',   # == worktree: dedup
    }

    def fake_git(*args):
        if args[0] == "log":
            return "aaa\nbbb\nccc\n"
        return blobs[args[1]]

    monkeypatch.setattr(bench_run, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench_run, "_git", fake_git)
    bench_run.trajectory()
    rows = capsys.readouterr().out.strip().splitlines()
    assert "trajectory,BENCH_t.json,aaa,__warning__,unreadable: " \
           "git show failed (renamed or missing at this revision)" in rows
    assert any(r.startswith("trajectory,BENCH_t.json,bbb,__warning__,"
                            "malformed JSON") for r in rows)
    assert "trajectory,BENCH_t.json,ccc,s.tok_s,2" in rows
    assert not any(",worktree," in r for r in rows)    # deduped vs ccc


# ------------------------------------------- sanitizers on the real engines

def _cfg(**kw):
    base = dict(name="ana", family="dense", num_layers=2, d_model=32,
                num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
                vocab_size=64)
    base.update(kw)
    return ModelConfig(**base)


def _mk_set(params, cfg, rank, seed):
    return init_adapter_set(params, jax.random.key(seed),
                            LoRAConfig(rank=rank, alpha=8.0,
                                       targets=cfg.lora_targets))


def test_serve_scheduled_guarded_zero_recompile_across_publish():
    """The acceptance bar: a RecompileGuard wrapped around the paged
    engines stays silent across a full serve with mid-serve publishes
    (wrap mode on run 1, watch mode proving zero growth on run 2)."""
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    sets = [_mk_set(params, cfg, 4, seed=30 + t) for t in range(3)]
    pub = _mk_set(params, cfg, 4, seed=77)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(6)]

    def run(guard):
        live = LiveAdapterBank.from_sets(sets, hot_slots=2)

        def on_boundary(i):
            if i == 2:
                live.publish(0, pub)         # resident hot swap mid-serve
                live.publish(2, pub)         # overflow host write

        reqs = [serve.Request(rid=i, prompt=prompts[i], steps=6,
                              adapter_id=i % 3) for i in range(6)]
        return serve.serve_scheduled(model, params, reqs, bank=live,
                                     max_batch=2, chunk=3, wait=False,
                                     on_boundary=on_boundary, guard=guard)

    g1 = RecompileGuard()
    run(g1)                                  # wrap mode: compiles are fresh sigs
    watch = RecompileGuard()
    watch.watch_model(model)                 # baselines after full warmup
    run(RecompileGuard())
    watch.check()                            # publish schedule: zero growth


def test_serve_scheduled_transfer_guarded():
    cfg = _cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
               for _ in range(3)]

    def mk():
        return [serve.Request(rid=i, prompt=prompts[i], steps=4)
                for i in range(3)]

    plain = serve.serve_scheduled(model, params, mk(), max_batch=2,
                                  chunk=2, wait=False)       # warm
    guarded = serve.serve_scheduled(model, params, mk(), max_batch=2,
                                    chunk=2, wait=False, transfer_guard=True)
    assert [r.tokens for r in plain] == [r.tokens for r in guarded]


def _tiny_trainer(track=False):
    cfg = _cfg()
    model = build_model(cfg)
    ds = FederatedDataset(64, 3, seq_len=16, batch_per_client=2, seed=0)
    return FederatedTrainer(
        model, ds, lora_cfg=LoRAConfig(rank=4, alpha=8.0),
        fed_cfg=FederatedConfig(num_clients=3, local_steps=1,
                                aggregation="fedsa"),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), seed=0,
        data_mode="device", chunk_rounds=2, track_stability=track)


def test_run_chunk_transfer_guarded_after_warmup():
    """The training engine holds all-device state: after one warm chunk, a
    guarded chunk runs clean, and a seeded numpy pytree leaf trips."""
    tr = _tiny_trainer()
    r0 = jnp.asarray(0, jnp.int32)
    aset, opt, key, _ = tr._run_chunk(tr.base, tr.adapters, tr.opt_state,
                                      tr._key, r0, num_rounds=2)
    run = guard_transfers(tr._run_chunk)
    aset, opt, key, ms = run(tr.base, aset, opt, key, r0 + 2, num_rounds=2)
    assert np.isfinite(np.asarray(ms["loss"])).all()
    opt_np = jax.tree.map(np.asarray, opt)   # un-staged state: must trip
    with pytest.raises(TransferGuardError):
        run(tr.base, aset, opt_np, key, r0 + 4, num_rounds=2)


def test_trainer_stability_report_end_to_end():
    tr = _tiny_trainer(track=True)
    with pytest.raises(ValueError, match="track_stability"):
        tr.stability_report()                # no history yet
    tr.run(4)
    assert all("update_norm" in h for h in tr.history)
    rep = tr.stability_report()
    assert rep.ok and rep.verdict == "stabilized"
    assert len(rep.norms) == 4


def test_track_stability_preserves_metric_values():
    """Opt-in update_norm must not perturb training itself: losses are
    bit-identical with and without the extra metric."""
    a, b = _tiny_trainer(track=False), _tiny_trainer(track=True)
    a.run(2)
    b.run(2)
    np.testing.assert_array_equal([h["loss"] for h in a.history],
                                  [h["loss"] for h in b.history])
