"""``correct`` fails where it must: the control, and the faults a cell of
this system can have, each planted under a run at a size a CPU holds.

The runs skip ``run.py``'s look for a chip and drive the rest of a run
(``harness.run``): set-up, the window, the check against the reference."""

import copy
import time

import jax
import jax.numpy as jnp
import pytest

from bench import control, harness, run

CELL = "kron_g500_s14.replay"
# The other arrival kinds of the general generator, at a CPU's pace.
ARRIVALS = {"replay": None,
            "open": {"arrivals": "poisson", "rate_per_s": 12.0},
            "saturate": {"arrivals": "saturate", "max_rate_per_s": 400.0}}


def tiny(traffic=None):
    """The cell at SCALE 7 on the jnp path, with a CPU's rate cap."""
    bench, cell, config, replay = run.load_cell(CELL)
    config = copy.deepcopy(config)
    config["graphs"].update(scale=7, edge_factor=8, pool=3)
    config["engine"].update(use_kernel=False)
    return bench, cell, config, traffic or dict(replay, max_rate_per_s=400.0)


def serve(monkeypatch, fault=None, seed=2**31 + 5, traffic=None):
    from repro.core import executor

    monkeypatch.setattr(executor, "_program_cache", type(
        executor._program_cache)())
    if fault is not None:
        monkeypatch.setattr(executor, "bucket_impl", fault(
            executor.bucket_impl))
    bench, cell, config, traffic = tiny(traffic)
    return harness.run(cell, config, traffic, bench, seed, 2.0, False,
                       time.perf_counter())


def altered_answer(impl):
    """A label of the first graph of every flush changed where produced."""
    def wrapped(*args, **kwargs):
        labels, costs, picked, rounds = impl(*args, **kwargs)
        return labels.at[0, 0].add(1), costs, picked, rounds
    return wrapped


def half_left_out(impl):
    """The second half of every flush's rows (its graphs' vertices) never
    clustered: a flush holds one graph here."""
    def wrapped(*args, **kwargs):
        labels, costs, picked, rounds = impl(*args, **kwargs)
        g, r = labels.shape
        keep = (jnp.arange(r) < r // 2)[None, :]
        own = jnp.broadcast_to(jnp.arange(r, dtype=labels.dtype), (g, r))
        return jnp.where(keep, labels, own), costs, picked, rounds
    return wrapped


def state_unchanged(impl):
    """The rounds loop hands back its starting state: every vertex its own
    cluster."""
    def wrapped(*args, **kwargs):
        labels, costs, picked, rounds = impl(*args, **kwargs)
        g, r = labels.shape
        own = jnp.broadcast_to(jnp.arange(r, dtype=labels.dtype), (g, r))
        return own, costs, picked, rounds
    return wrapped


@pytest.mark.parametrize("arrivals", sorted(ARRIVALS))
def test_sound_run_is_correct(monkeypatch, arrivals):
    out = serve(monkeypatch, traffic=ARRIVALS[arrivals])
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    if arrivals == "open":
        assert out["attempted"] == 24
    assert list(out)[-1] == "checks"
    assert all(c == {"value": 0, "limit": 0} for c in out["checks"].values())
    assert out["metrics"]["graphs_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", [altered_answer, half_left_out,
                                   state_unchanged])
def test_fault_is_not_correct(monkeypatch, fault):
    out = serve(monkeypatch, fault)
    assert not out["correct"]
    assert out["checks"]["labels_differ"]["value"] > 0
    assert out["failed"] > 0


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 9])
def test_control_is_not_correct(seed):
    _, _, config, traffic = tiny()
    got = control.numbers(jax, config, traffic, seed, 2.0, requests=12)
    assert got["plan_differs"] > 0
