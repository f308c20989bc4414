"""The serving view of the frozen base (``serve.serving_base``).

Where every dot already rounds a float32 weight to bfloat16 (XLA on a TPU at
the default matmul precision), ``serve_scheduled`` casts the leaves the
model reads only as dot operands to bfloat16 once per run.  Here, on the CPU:

  * leaf selection: exactly the projections and an untied head are cast;
    the embedding, norms, packed (quantized) and narrower leaves are not;
  * equivalence: serving with the cast leaves gives the tokens that float32
    params rounded to bfloat16 give, so every cast leaf reaches only dots;
  * the gate: on the CPU, under a float32/highest matmul precision and on a
    Pallas tier the engines get ``params`` unchanged and nothing is counted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace
from repro.configs.base import LoRAConfig, ModelConfig
from repro.core.lora import AdapterBank, init_adapter_set
from repro.core.quant import QuantizedLinear, quantize_tree
from repro.launch import serve
from repro.models.api import build_model

PROJECTIONS = {"q", "k", "v", "o", "w_up", "w_gate", "w_down"}


def _cfg(**kw):
    base = dict(name="view", family="dense", num_layers=2, d_model=32,
                num_heads=2, num_kv_heads=1, head_dim=16, d_ff=64,
                vocab_size=64, qk_norm=True, norm="rmsnorm",
                mlp_variant="swiglu", tie_embeddings=False)
    base.update(kw)
    return ModelConfig(**base)


CONFIGS = {"qwen3-like": _cfg(),
           "tied": _cfg(tie_embeddings=True, qk_norm=False,
                        norm="layernorm")}


def _names(tree):
    """{path: leaf} with paths as tuples of dict keys; packed leaves whole."""
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, QuantizedLinear))
    return {tuple(k.key for k in path): leaf for path, leaf in flat}


def _gate_on(monkeypatch):
    monkeypatch.setattr(serve, "_dots_round_to_bf16", lambda m: True)


def _rounded(model, params):
    """float32 params whose dot-only leaves hold bfloat16-rounded values."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return treedef.unflatten([
        leaf.astype(jnp.bfloat16).astype(jnp.float32)
        if model.dot_only(tuple(k.key for k in path)) else leaf
        for path, leaf in flat])


class _Seen:
    """``guard=`` for serve_scheduled that records the params each engine
    call receives."""

    def __init__(self):
        self.params = []

    def wrap(self, name, fn):
        def call(params, *args, **kw):
            self.params.append(params)
            return fn(params, *args, **kw)
        return call


def _serve(model, params, *, bank=None, guard=None, B=2, p=5, steps=7):
    prompt = np.asarray(jax.random.randint(jax.random.key(2), (B, p), 0,
                                           model.cfg.vocab_size), np.int32)
    reqs = [serve.Request(rid=i, prompt=prompt[i], steps=steps,
                          adapter_id=i % (bank.size if bank else 1))
            for i in range(B)]
    done = serve.serve_scheduled(model, params, reqs, bank=bank, max_batch=B,
                                 block_size=4, chunk=3, wait=False,
                                 guard=guard)
    return np.stack([np.asarray(r.tokens) for r in done])


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_view_casts_exactly_the_dot_only_leaves(monkeypatch, name):
    _gate_on(monkeypatch)
    model = build_model(CONFIGS[name])
    params = model.init(jax.random.key(0))
    with trace.tracing() as t:
        view = serve.serving_base(model, params)
    got = _names(view)
    cast = {path for path, leaf in got.items() if leaf.dtype == jnp.bfloat16}
    want = {path for path in got
            if path[-1] in PROJECTIONS or path == ("lm_head",)}
    assert cast == want
    assert ("embed",) in got and ("embed",) not in cast
    assert all(got[path].dtype == jnp.float32 for path in got
               if path[-1].endswith(("_scale", "_bias")))
    for path in want:
        np.testing.assert_array_equal(
            np.asarray(got[path]),
            np.asarray(_names(params)[path].astype(jnp.bfloat16)))
    n = len(want)
    assert t.counters["serve.base_bf16_leaves"] == n
    (prep,) = t.named("serve.prepare")
    assert prep.attrs == {"leaves": n,
                          "bytes": sum(got[path].size * 2 for path in want)}


@pytest.mark.parametrize("base", ["int8", "bfloat16"])
def test_view_leaves_packed_and_narrow_leaves(monkeypatch, base):
    _gate_on(monkeypatch)
    if base == "int8":
        model = build_model(_cfg())
        params = quantize_tree(model.init(jax.random.key(0)), "int8")
    else:
        model = build_model(_cfg(param_dtype="bfloat16"))
        params = model.init(jax.random.key(0))
    view = _names(serve.serving_base(model, params))
    for path, leaf in _names(params).items():
        if isinstance(leaf, QuantizedLinear) or leaf.dtype != jnp.float32:
            assert view[path] is leaf, path
        elif path != ("lm_head",):
            assert view[path] is leaf, path
        else:
            assert view[path].dtype == jnp.bfloat16
    if base == "bfloat16":
        assert serve.serving_base(model, params) is params


@pytest.mark.parametrize("name,banked", [("qwen3-like", False),
                                         ("qwen3-like", True),
                                         ("tied", True)])
def test_view_serves_the_tokens_of_rounded_weights(monkeypatch, name,
                                                   banked):
    model = build_model(CONFIGS[name])
    params = model.init(jax.random.key(0))
    bank = None
    if banked:
        bank = AdapterBank.from_sets([init_adapter_set(
            params, jax.random.fold_in(jax.random.key(1), i),
            LoRAConfig(rank=4, alpha=8.0, targets=model.cfg.lora_targets),
            n_clients=2) for i in range(2)])
    rounded = _serve(model, _rounded(model, params), bank=bank)
    _gate_on(monkeypatch)
    seen = _Seen()
    viewed = _serve(model, params, bank=bank, guard=seen)
    np.testing.assert_array_equal(viewed, rounded)
    for p in seen.params:
        assert p["stack"]["repeat"]["p0"]["attn"]["q"].dtype == jnp.bfloat16
        assert p["embed"].dtype == jnp.float32


def test_engines_get_params_unchanged_on_cpu():
    model = build_model(_cfg())
    params = model.init(jax.random.key(0))
    seen = _Seen()
    with trace.tracing() as t:
        _serve(model, params, guard=seen)
    assert seen.params and all(p is params for p in seen.params)
    assert t.counters["serve.base_bf16_leaves"] == 0
    assert not t.named("serve.prepare")


@pytest.mark.parametrize("backend,precision,use_pallas,cast", [
    ("cpu", None, False, False),
    ("tpu", "highest", False, False),
    ("tpu", "float32", False, False),
    ("tpu", None, True, False),
    ("tpu", None, False, True),
    ("tpu", "default", False, True),
    ("tpu", "bfloat16", False, True),
])
def test_gate_follows_backend_precision_and_tier(monkeypatch, backend,
                                                 precision, use_pallas,
                                                 cast):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model = build_model(_cfg(use_pallas=use_pallas))
    params = model.init(jax.random.key(0))
    with trace.tracing() as t, jax.default_matmul_precision(precision):
        view = serve.serving_base(model, params)
    assert (view is not params) == cast
    assert t.counters["serve.base_bf16_leaves"] == (8 if cast else 0)
