"""A whole run of the benchmark with the timed path broken underneath
must come out not ``correct``: the run is driven here on the CPU, past
the harness's look for a chip, once per fault a cell can have."""
import time

import jax
import jax.numpy as jnp
import pytest

import _paths
from harness import cell as cell_mod
from harness.spec import load_cell


def _stale(fns):
    """Half the requests left out: every other request is answered with
    the answer of the one before it."""
    last = fns[-1]
    state = {"n": 0, "prev": None}

    def run(b):
        out = last(b)
        state["n"] += 1
        if state["n"] % 2 == 0 and state["prev"] is not None:
            return state["prev"]
        state["prev"] = out
        return out

    return fns[:-1] + [run]


def _unchanged(fns):
    """A stage that returns its input unchanged."""
    return fns[:-1] + [lambda b: b]


def _no_exchange(fns):
    """The hop between chips left out: stage 1 reads zeros where stage 0's
    boundary should have arrived."""
    second = fns[1]
    return [fns[0], lambda b: second(jax.tree.map(jnp.zeros_like, b))] \
        + fns[2:]


def _altered(fns):
    """The answer altered where it is produced: one logit of each answer
    moved by a tenth of the largest."""
    last = fns[-1]

    def run(b):
        out = dict(last(b))
        for k, v in out.items():
            if v.ndim == 2:
                out[k] = v.at[0, 7].add(0.1 * jnp.abs(v).max())
        return out

    return fns[:-1] + [run]


def _sound(fns):
    return fns


def _run(monkeypatch, breaks, stages):
    real = cell_mod.build_stage_fns
    monkeypatch.setattr(cell_mod, "build_stage_fns",
                        lambda *a, **k: breaks(real(*a, **k)))
    monkeypatch.setattr(cell_mod, "use_compile_cache", lambda: None)
    cell = load_cell(_paths.ROOT, "resnet50.c1.steady")
    cell.config = dict(cell.config, stages=stages)
    cell.traffic = dict(cell.traffic, rate_per_s=4, pool=3)
    return cell_mod.run_cell(cell, 2**31 + 5, 1.0, False, jax.devices()[:1],
                             time.perf_counter(), log=lambda m: None)


@pytest.mark.parametrize("breaks,stages", [
    (_unchanged, 1), (_stale, 1), (_no_exchange, 2), (_altered, 1)],
    ids=["state-unchanged", "half-left-out", "no-exchange", "altered"])
def test_broken_timed_path_is_not_correct(monkeypatch, breaks, stages):
    result = _run(monkeypatch, breaks, stages)
    assert result["attempted"] == 4
    assert result["correct"] is False, result["checks"]
    assert list(result)[-1] == "checks"


def test_sound_timed_path_is_correct(monkeypatch):
    result = _run(monkeypatch, _sound, 2)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] == 4
    assert set(result["metrics"]) == {"latency_p50_ms", "setup_s"}
