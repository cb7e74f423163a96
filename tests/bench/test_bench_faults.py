"""The timed path broken underneath: ``correct`` must come out false.

Each fault is planted in the program for one run at a small size on the
CPU, with the harness's look for a chip skipped.  The fault of a missing
exchange between chips does not apply: every cell runs on one chip."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.control_plane as control_plane
import repro.core.gp as gp
from tinybench import tiny_root  # noqa: F401  (a fixture)
from bench.harness import run_cell
from repro.core.control_plane import ControlPlane

CELLS = {"tiny-churn-steady": 1.0, "tiny-lc-steady": 1.0,
         "tiny-lc-saturated": 0.3}


def run(root, cell):
    line, _ = run_cell(cell, 2**33 + 3, CELLS[cell], False,
                       t_start=time.perf_counter(), require_tpu=False,
                       root=root)
    return line


def fold_returns_its_state(monkeypatch):
    def stale(W, alpha, diag_acc, K_row, idx, z_val, mu0_val, k, jitter):
        return W, alpha, diag_acc, K_row[idx]
    monkeypatch.setattr(gp, "_append_step", stale)


def half_the_pool_left_out(monkeypatch):
    score = control_plane.choose_next_fused

    def half(mu, sd, best, membership, cost, selected):
        odd = (jnp.arange(selected.shape[0]) % 2) == 1
        return score(mu, sd, best, membership, cost, selected | odd)
    monkeypatch.setattr(control_plane, "choose_next_fused", half)


def pick_altered(monkeypatch):
    choose = ControlPlane.choose_mdmt

    def altered(self, device_speed=1.0):
        pick = choose(self, device_speed)
        if pick is None:
            return None
        free = np.flatnonzero(~self.selected & self.model_live)
        free = free[free != pick[0]]
        return (int(free[0]), pick[1]) if len(free) else pick
    monkeypatch.setattr(ControlPlane, "choose_mdmt", altered)


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("fault", [fold_returns_its_state,
                                   half_the_pool_left_out, pick_altered])
def test_a_broken_path_is_not_correct(tiny_root, monkeypatch, cell, fault):
    fault(monkeypatch)
    line = run(tiny_root, cell)
    assert line["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in line["checks"].values())
