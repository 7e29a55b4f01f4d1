"""The correctness comparison fails what it must, at a size a test run can
hold: tiny copies of the cells (tests/tiny.py) on the CPU, the rest of a
run as on the chip (set-up, window, reference), and limits read at this
size the way chipbench/limits/*.json were read at the cells' own:

* the control (the reference computed in float8 in the program's place)
  reads above the limit that sound runs stay under;
* a fault planted in the timed path turns ``correct`` false: a served
  token altered where the decode burst produces it; a training step that
  returns its state unchanged; a step that leaves out half of its batch.
"""
import jax.numpy as jnp
import pytest

import tiny

# at the tiny size, program over two seeds / control over two seeds:
# widest gap 0.000-0.002 / 0.039-0.047; first-gradient gap
# 0.0006-0.0017 / 0.015-0.026; change gap 0.0009-0.0020 / 0.009-0.011;
# the loss gap (1.1e-5-2.6e-5 / 5.8e-5-1.3e-4) does not separate at this
# size and is held loosely
TINY = {
    "widest_gap": {"limit": 0.015},
    "loss_gap": {"limit": 1e-3},
    "grad_gap": {"limit": 0.006},
    "change_gap": {"limit": 0.005},
}
SERVE = ("sc2-3b.complete", "sc2-3b.batch-gen")


def _limits(workload):
    keys = (("widest_gap",) if workload in SERVE
            else ("loss_gap", "grad_gap", "change_gap"))
    return {k: TINY[k] for k in keys}


def _run(workload, seed, **kw):
    return tiny.run(workload, seed=seed, limits_override=_limits(workload),
                    **kw)


@pytest.mark.parametrize("workload", SERVE + ("bert-base.train",))
def test_sound_runs_pass_and_the_control_fails(workload):
    r = _run(workload, 21, control=True)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    failed = [k for k, v in r["control"].items() if v > TINY[k]["limit"]]
    assert failed, r["control"]
    if workload == "bert-base.train":
        # the half-batch fault read in the reference put in the program's
        # place fails too
        half = r["faults"]["half_batch"]
        assert any(v > TINY[k]["limit"] for k, v in half.items()), half


@pytest.mark.parametrize("workload", SERVE)
def test_a_token_altered_in_the_burst_fails(workload, monkeypatch):
    from repro.serve.engine import Engine
    make = Engine._make_burst

    def broken(self, k, eos_id):
        burst = make(self, k, eos_id)

        def run(*a):
            out = burst(*a)
            toks = out[4].at[:, 0].set((out[4][:, 0] + 1) % 7)
            return out[:4] + (toks,) + out[5:]
        return run

    monkeypatch.setattr(Engine, "_make_burst", broken)
    r = _run(workload, 22)
    assert not r["correct"], r["checks"]


def _broken_step(monkeypatch, fault):
    import repro.launch.train as lt
    make = lt.make_train_step

    def broken(*a, **kw):
        step = make(*a, **kw)

        def run(params, opt_state, batch):
            if fault == "half_batch":
                half = batch["tokens"].shape[0] // 2
                batch = {k: v[:half] for k, v in batch.items()}
            p, o, metrics = step(params, opt_state, batch)
            if fault == "unchanged":
                return params, opt_state, metrics
            return p, o, metrics
        return run

    monkeypatch.setattr(lt, "make_train_step", broken)


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_a_broken_training_step_fails(fault, monkeypatch):
    _broken_step(monkeypatch, fault)
    r = _run("bert-base.train", 23)
    assert not r["correct"], r["checks"]
    assert r["failed"] == 0
    assert jnp.isfinite(r["checks"]["loss_gap"]["value"])
