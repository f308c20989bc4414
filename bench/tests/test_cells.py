"""Each cell driver at a tiny size on the CPU: a sound run comes out
correct, and a run with the timed path broken underneath comes out not
correct, once for each fault the cell can have."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH, ROOT

import serve_cell
import train_cell


def test_train_cell_sound(ctx_for):
    result, checks, _ = train_cell.run(ctx_for("train"))
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


def test_serve_cell_sound(ctx_for):
    result, checks, _ = serve_cell.run(ctx_for("serve"))
    assert result["correct"], checks
    assert result["attempted"] == 20 and result["failed"] == 0
    assert set(result["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                      "serve_tokens_per_s", "setup_s"}


def test_train_state_unchanged_is_caught(ctx_for, monkeypatch):
    """A round engine that hands back the state it was given."""
    from repro.core.federated import FederatedTrainer
    build = FederatedTrainer._build_engine

    def stuck(self):
        build(self)
        inner = self._run_chunk

        def run_chunk(base, adapters, opt, key, round0, **kw):
            keep = jax.tree.map(jnp.copy, adapters)
            _, opt, key, ms = inner(base, adapters, opt, key, round0, **kw)
            return keep, opt, key, ms

        self._run_chunk = run_chunk

    monkeypatch.setattr(FederatedTrainer, "_build_engine", stuck)
    result, checks, _ = train_cell.run(ctx_for("train"))
    assert not result["correct"], checks


def test_train_half_batch_is_caught(ctx_for, monkeypatch):
    """A loss over half of each client's rows."""
    from repro.models.api import Model
    loss = Model.loss

    def half(self, params, batch, adapters=None):
        toks = batch["tokens"]
        return loss(self, params, {**batch,
                                   "tokens": toks[: toks.shape[0] // 2]},
                    adapters=adapters)

    monkeypatch.setattr(Model, "loss", half)
    result, checks, _ = train_cell.run(ctx_for("train"))
    assert not result["correct"], checks


def _patch_chunk(monkeypatch, change):
    from repro.launch import serve
    build = serve._jit_paged_chunk
    monkeypatch.setattr(serve, "_jit_paged_chunk",
                        lambda model: change(build(model)))


def test_serve_token_altered_is_caught(ctx_for, monkeypatch):
    """A decode chunk that emits another token than it computed."""
    def altered(fn):
        def chunk(params, cache, tok, pos, active, table, adapters, *,
                  steps):
            cache, tok, pos, toks = fn(params, cache, tok, pos, active,
                                       table, adapters, steps=steps)
            return cache, tok, pos, toks.at[:, -1].add(1)
        return chunk

    _patch_chunk(monkeypatch, altered)
    result, checks, _ = serve_cell.run(ctx_for("serve"))
    assert not result["correct"], checks


def test_serve_state_unchanged_is_caught(ctx_for, monkeypatch):
    """A decode chunk that hands back the KV cache it was given."""
    def stale(fn):
        def chunk(params, cache, tok, pos, active, table, adapters, *,
                  steps):
            _, tok, pos, toks = fn(params, cache, tok, pos, active, table,
                                   adapters, steps=steps)
            return cache, tok, pos, toks
        return chunk

    _patch_chunk(monkeypatch, stale)
    result, checks, _ = serve_cell.run(ctx_for("serve"))
    assert not result["correct"], checks


@pytest.mark.parametrize("workload", ["train-stablelm-1.6b-n8r64",
                                      "serve-qwen3-8b-l6-poisson"])
def test_command_refuses_without_a_tpu(workload):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_command_refuses_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no system to
    measure."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "train-stablelm-1.6b-n8r64", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert out.returncode != 0 and out.stdout == ""
