"""The layer scan's saved set: the backward keeps the named projection
outputs (``transformer.SAVEABLE``) instead of recomputing their matmuls,
without changing a round's numbers, and the round chooses the set from its
shapes and the device's memory."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace
from repro.configs import get_config
from repro.configs.base import (FederatedConfig, LoRAConfig, ModelConfig,
                                OptimizerConfig)
from repro.core import federated
from repro.core.federated import FederatedTrainer
from repro.core.lora import init_lora
from repro.data.synthetic import FederatedDataset
from repro.models.api import build_model
from repro.models.transformer import SAVEABLE, saveable_widths

N, SEQ, BATCH, STEPS, RANK = 3, 16, 2, 2, 4

TINY = {
    "mha": dict(num_heads=4, num_kv_heads=4, head_dim=16),
    "gqa_qknorm": dict(num_heads=4, num_kv_heads=2, head_dim=16,
                       qk_norm=True),
}


@pytest.fixture(scope="module", params=sorted(TINY))
def tiny(request):
    cfg = ModelConfig(name=request.param, family="dense", num_layers=2,
                      d_model=64, d_ff=128, vocab_size=64,
                      **TINY[request.param])
    model = build_model(cfg)
    return model, model.init(jax.random.key(0))


def make_trainer(model, base, buffer_size):
    ds = FederatedDataset(64, N, seq_len=SEQ, batch_per_client=BATCH,
                          seed=0)
    return FederatedTrainer(
        model, ds, lora_cfg=LoRAConfig(rank=RANK),
        fed_cfg=FederatedConfig(num_clients=N, local_steps=STEPS,
                                aggregation="fedsa",
                                buffer_size=buffer_size),
        opt_cfg=OptimizerConfig(name="sgd", lr=0.05), seed=0,
        base_params=base, chunk_rounds=2)


@pytest.mark.parametrize("buffer_size", [None, 0],
                         ids=["synchronous", "buffered"])
def test_full_set_matches_empty_set(tiny, buffer_size, monkeypatch):
    """Two rounds with every name kept read as with none kept: the
    forward values are kept instead of computed twice."""
    model, base = tiny
    plain = make_trainer(model, base, buffer_size)
    plain.run(2)
    monkeypatch.setattr(federated, "device_bytes_limit", lambda: 1 << 50)
    with trace.tracing() as t:
        kept = make_trainer(model, base, buffer_size)
        kept.run(2)
    assert t.named("fed.saved")[0].attrs["saved"] == list(SAVEABLE)
    assert t.counters["fed.saved_residual_bytes"] > 0
    for a, b in zip(plain.history, kept.history):
        np.testing.assert_allclose(b["loss"], a["loss"], rtol=1e-6)
    for x, y in zip(jax.tree.leaves(plain.lora), jax.tree.leaves(kept.lora)):
        np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-6,
                                   atol=1e-6 * float(jnp.abs(x).max()))


def _count(jaxpr, prim):
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == prim
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if isinstance(inner, jax.extend.core.Jaxpr):
                    n += _count(inner, prim)
    return n


def test_each_name_spares_its_matmul(tiny):
    """Each kept name spares the backward at least its own matmul (q and
    v their adapter's too, the attention output the probabilities times
    v), also where qk-norm's backward reads q and k; the savings add up.
    The layer scan's body is traced once, so the counts are per layer."""
    model, base = tiny
    lora = init_lora(base, jax.random.key(1), LoRAConfig(rank=RANK))
    batch = {"tokens": jnp.zeros((BATCH, SEQ), jnp.int32)}

    def dots(saved):
        grad = jax.grad(lambda lo: model.loss(base, batch, adapters=lo,
                                              saved=saved)[0])
        return _count(jax.make_jaxpr(grad)(lora).jaxpr, "dot_general")

    assert set(saveable_widths(model.cfg)) == set(SAVEABLE)
    spared = {n: dots(()) - dots((n,)) for n in SAVEABLE}
    assert all(k >= 1 for k in spared.values()), spared
    assert dots(()) - dots(SAVEABLE) == sum(spared.values())


# ------------------------------------------------------- the chooser

GIB = 1 << 30


def round_shapes(arch, *, clients, rows, seq, steps, rank):
    """Abstract arguments of one round: nothing is allocated."""
    model = build_model(get_config(arch))
    base = jax.eval_shape(model.init, jax.random.key(0))
    lora = jax.eval_shape(
        lambda b: jax.tree.map(
            lambda x: jnp.broadcast_to(x, (clients,) + x.shape),
            init_lora(b, jax.random.key(1),
                      LoRAConfig(rank=rank, targets=("q", "v")))), base)
    opt = {"t": jax.ShapeDtypeStruct((clients,), jnp.int32)}
    batches = {"tokens": jax.ShapeDtypeStruct((clients, steps, rows, seq),
                                              jnp.int32)}
    return model, base, lora, opt, batches


@pytest.fixture(scope="module")
def stablelm():
    return round_shapes("stablelm-1.6b", clients=8, rows=1, seq=256,
                        steps=2, rank=64)


def test_chooser_fits_the_one_chip_training_cell(stablelm):
    """Under a v5e's 15.75 GiB the cell's round keeps q, k, v, the
    attention output and the gate: the set a compile for a described v5e
    accepts, at a peak of 14.421 GB."""
    names, nbytes = federated.saved_residuals(*stablelm, int(15.75 * GIB))
    assert set(names) >= {"q", "k", "v", "attn_out"}
    assert names == ("q", "k", "v", "attn_out", "mlp_gate")
    assert nbytes == 24 * 8 * 256 * (4 * 2048 + 5632) * 4


def test_chooser_keeps_nothing_without_room(stablelm):
    rest = federated.round_rest_bytes(*stablelm)
    no_room = int(rest / (1 - federated.SAVED_MARGIN))
    assert federated.saved_residuals(*stablelm, no_room) == ((), 0)
    assert federated.saved_residuals(*stablelm, None) == ((), 0)


def test_chooser_within_the_four_chip_headroom():
    """Qwen3-8B, N 2, rank 256, 1 x 512 tokens on a 1x4 mesh compiles at
    15.355 GB of 16.91 a chip: what the round keeps must fit the 1.55 GB
    left (the chooser counts global shapes, so it keeps nothing)."""
    shapes = round_shapes("qwen3-8b", clients=2, rows=1, seq=512, steps=2,
                          rank=256)
    _, nbytes = federated.saved_residuals(*shapes, int(15.75 * GIB))
    assert nbytes <= 1.55e9


def test_cpu_reports_no_limit():
    """The CPU keeps the empty set, so its executables are unchanged."""
    if jax.default_backend() != "cpu":
        pytest.skip("the CPU's behaviour")
    assert federated.device_bytes_limit() is None
