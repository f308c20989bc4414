"""The family layer: how a configuration names its architecture's module,
what that module must provide, and that the cell drivers reach the
reference, the work counts and the adapter layout only through it."""
import json
import os

import jax
import pytest

from conftest import BENCH, DATA, cell_ctx

import harness
import model as bmodel
import serve_cell
import train_cell

FAMILIES = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "families"))
                  if f.endswith(".py") and f != "__init__.py")


@pytest.mark.parametrize("name,directory", [("stablelm-1.6b", None),
                                            ("qwen3-8b-l6", None),
                                            ("tiny-layernorm", DATA)])
def test_config_without_a_family_is_dense(name, directory):
    cfg = bmodel.load_config(name, directory)
    assert "family" not in cfg
    assert bmodel.family(cfg) is bmodel.load_family("dense")


def _write_config(tmp_path, **changes):
    with open(os.path.join(DATA, "tiny-layernorm.json")) as f:
        cfg = {**json.load(f), **changes}
    with open(tmp_path / "odd.json", "w") as f:
        json.dump(cfg, f)
    return cfg


@pytest.mark.parametrize("build", [
    lambda cfg, mix: train_cell.build(cfg, mix, 1, harness.Spans()),
    lambda cfg, mix: serve_cell.build(cfg, mix, 1)], ids=["train", "serve"])
def test_unknown_family_fails_before_device_work(tmp_path, build):
    cfg = _write_config(tmp_path, family="no-such")
    with pytest.raises(ValueError, match=r"'no-such'.*known: \[.*'dense'"):
        bmodel.load_config("odd", str(tmp_path))
    live = len(jax.live_arrays())
    with pytest.raises(ValueError, match="'dense'"):
        build(cfg, {})
    assert len(jax.live_arrays()) == live


@pytest.mark.parametrize("name", FAMILIES)
def test_family_has_the_interface(name):
    fam = bmodel.load_family(name)
    for f in bmodel.INTERFACE + ("make_params",):
        assert callable(getattr(fam, f, None)), (name, f)


def test_family_lacking_a_function_is_refused(tmp_path, monkeypatch):
    (tmp_path / "half.py").write_text(
        "def forward(cfg, params, tokens, lora=None, gamma=1.0):\n"
        "    raise NotImplementedError\n")
    monkeypatch.setattr(bmodel, "FAMILY_DIRS",
                        bmodel.FAMILY_DIRS + [str(tmp_path)])
    with pytest.raises(ValueError, match="lacks.*'loss'") as e:
        bmodel.load_family("half")
    assert "'forward'" not in str(e.value)


def test_dense_refuses_what_it_does_not_compute():
    import dataclasses
    from repro.configs import get_config
    cfg = bmodel.load_config("tiny-layernorm", DATA)
    registry = get_config(cfg["arch"])
    fam = bmodel.load_family("dense")
    for change in ({"parallel_residual": True}, {"attn_window": 64},
                   {"attn_logit_softcap": 50.0}):
        odd = dataclasses.replace(registry, **change)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr("repro.configs.get_config", lambda name: odd)
            with pytest.raises(ValueError, match=r"names a family"):
                fam.program_config(cfg)


def _ask_for_work(rctx):
    """A per-layer reader that asks the cell for its work counts, as the
    mfu readers do."""
    if "chunk_work" in rctx:
        rctx["chunk_work"]({"positions": [3, 7], "tenants": 2, "steps": 2})
    return {}


def test_cells_reach_the_family_only_through_the_loader(tmp_path,
                                                        monkeypatch):
    """Each cell's tiny configuration, naming the probe family
    (``data/probe.py``: dense, with its calls recorded), runs correct and
    traced, and the two cells between them call every function of the
    interface."""
    monkeypatch.setattr(bmodel, "FAMILY_DIRS", bmodel.FAMILY_DIRS + [DATA])
    # the CPU has no published peaks; a traced run reads the v5e's
    v5e = harness.peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks", lambda kind: v5e)
    probe = bmodel.load_family("probe")
    probe.CALLS.clear()
    for kind, driver in (("train", train_cell), ("serve", serve_cell)):
        ctx = cell_ctx(kind, tmp_path / kind, trace=1)
        ctx.update(cfg={**ctx["cfg"], "family": "probe"},
                   per_layer=_ask_for_work)
        assert bmodel.family(ctx["cfg"]) is probe
        result, checks, _ = driver.run(ctx)
        assert result["correct"], (kind, checks)
    assert probe.CALLS == set(bmodel.INTERFACE) | {"make_params"}
