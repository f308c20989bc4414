"""The control at a size a test run holds: the reference computed in
bfloat16, put in the program's place, must fail the cell's limits."""
import jax

from conftest import cell_ctx

import harness
import model as bmodel
import serve_cell
import train_cell


def test_train_control_fails_the_limits(tmp_path):
    ctx = cell_ctx("train", tmp_path)
    cfg, mix, seed = ctx["cfg"], ctx["mix"], ctx["args"].seed
    from repro.models.api import build_model
    fam = bmodel.family(cfg)
    key = bmodel.seed_key(seed)
    params = fam.make_params(build_model(fam.program_config(cfg)),
                             jax.random.fold_in(key, 1))
    ref = train_cell.reference_rounds(cfg, mix, params, seed, key)
    low = train_cell.reference_rounds(cfg, mix, params, seed, key,
                                      dtype="bfloat16")
    ok, checks = harness.judge(train_cell.readings(low, ref), ctx["limits"])
    assert not ok, checks


def test_serve_control_fails_the_limits(tmp_path):
    # at this vocabulary bfloat16 rarely changes the top token; on this seed
    # it does (mean gap 9.1e-4, float32 reads 0)
    ctx = cell_ctx("serve", tmp_path, seed=5)
    cfg, mix, seed = ctx["cfg"], ctx["mix"], ctx["args"].seed
    state = serve_cell.build(cfg, mix, seed)
    reqs = serve_cell.window_requests(mix, 1.0, seed, cfg["vocab_size"])
    from repro.launch.serve import serve_scheduled
    done = serve_scheduled(state["model"], state["params"], reqs,
                           bank=state["bank"], wait=False,
                           **serve_cell.serve_kwargs(mix))
    sample = serve_cell.sample_for_check(done, len(done), seed)
    _, ctrl = serve_cell.logit_gaps(cfg, mix, state["params"],
                                    state["bank_lora"], sample, control=True)
    ok, checks = harness.judge({"mean_gap": float(ctrl.mean())},
                               ctx["limits"])
    assert not ok, checks
