"""``correct`` must come out false when the timed path is broken, and the
lower-precision control must fail the comparison: the rest of a run
(everything after the look for a chip) is driven on the CPU at a tiny size
with a fault planted underneath."""
import io
import json
import time
import types
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, run
from chipbench.harness import load_json
from conftest import DATA, TRAFFIC


def run_cell(spec, workload, seconds=1.0):
    """One run of a tiny cell; returns its result line."""
    wl = next(w for w in spec["workloads"] if w["name"] == workload)
    args = types.SimpleNamespace(workload=workload, seed=2**31 + 11,
                                 seconds=seconds, trace=0)
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.run_cell(spec, wl, args, jax.devices()[:1], TRAFFIC)
    assert rc == 0
    return json.loads(buf.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["tiny.search", "tiny.churn"])
def test_sound_run_is_correct(tiny_spec, workload):
    res = run_cell(tiny_spec, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def test_answer_altered_where_produced(tiny_spec, monkeypatch):
    """The descent's answer loses its nearest neighbour: every id served
    is still live, but the set is not the k nearest."""
    from repro.serve import frontend
    real = frontend.pinned_knn

    def altered(pinned, queries, *, k, max_frontier):
        d, i = real(pinned, queries, k=k + 1, max_frontier=max_frontier)
        return d[:, 1:], i[:, 1:]

    monkeypatch.setattr(frontend, "pinned_knn", altered)
    res = run_cell(tiny_spec, "tiny.search")
    assert not res["correct"]
    assert res["checks"]["answer_dist_gap"]["value"] > 1e-3


def test_step_returns_its_state_unchanged(tiny_spec, monkeypatch):
    """Mutation batches are acknowledged but the tree never changes."""
    from repro.stream import batcher

    def unchanged(self, ops, xs, oids):
        time.sleep(0.02)              # as long as a tiny batch takes
        st = np.full(len(ops), batcher.ST_APPLIED, np.int32)
        return batcher.BatchResult(st, len(ops), 0, 1)

    monkeypatch.setattr(batcher.MutationBatcher, "apply", unchanged)
    res = run_cell(tiny_spec, "tiny.churn")
    assert not res["correct"]
    assert res["checks"]["lost_writes"]["value"] > 0


def test_half_of_each_batch_left_out(tiny_spec, monkeypatch):
    """Each mutation batch applies its first half only."""
    from repro.stream import batcher
    real = batcher.MutationBatcher.apply

    def half(self, ops, xs, oids):
        n = len(ops) // 2
        res = real(self, ops[:n], xs[:n], oids[:n])
        res.statuses = np.concatenate(
            [res.statuses, np.full(len(ops) - n, batcher.ST_APPLIED,
                                   np.int32)])
        return res

    monkeypatch.setattr(batcher.MutationBatcher, "apply", half)
    res = run_cell(tiny_spec, "tiny.churn")
    assert not res["correct"]
    assert res["checks"]["lost_writes"]["value"] > 0


@pytest.mark.parametrize("workload", ["tiny.search", "tiny.churn"])
def test_bfloat16_control_fails(tiny_cells, workload):
    """The plain reference in bfloat16, put in the program's place over a
    window's queries, fails the configuration's limits."""
    cell = tiny_cells(workload)
    rec = cell.window(5, 1.0)
    sound = check.numbers(cell, rec)
    assert check.compare(sound, cell.cfg["limits"])[0], sound
    rec = cell.window(5, 1.0)
    ctl = check.control_numbers(cell, rec, jnp.bfloat16)
    limits = cell.cfg["limits"]
    assert not check.compare(ctl, limits)[0], ctl
    assert ctl["answer_dist_gap"] > limits["answer_dist_gap"]


def test_limits_cover_every_number(tiny_spec):
    """Every configuration states a limit for every number the checks
    can produce."""
    names = {"unanswered", "bad_ids", "answer_dist_gap", "answer_id_gap",
             "lost_writes", "replay_lost_writes", "replay_mismatch"}
    full = load_json(DATA.parents[2] / "BENCHMARK.json")
    for c in full["configs"] + tiny_spec["configs"]:
        assert set(load_json(DATA.parents[2] / c["file"])["limits"]) == names
