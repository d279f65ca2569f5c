"""The comparison that decides ``correct``.

The program's first training steps are held against the plain float32
reference's on three kinds of number (see PERF.md, "How correct is
decided"): every step's loss; the norm of every leaf of the first
gradient as the optimizer got it; the norm of every leaf of the
parameters' change after the last step. The two norms are taken by the
worst leaf: the gap between the program's norm and the reference's,
against the reference's norm of that leaf or of the median leaf,
whichever is larger (some gradients are all but zero). Every number
compared is returned beside its limit, and the harness prints them.
"""

from __future__ import annotations

import math

import numpy as np


def leaf_gaps(got, want):
    """Every leaf's relative gap between two vectors of leaf norms."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        raise ValueError(f"{got.shape} norms against {want.shape}")
    gap = np.abs(got - want) / np.maximum(want, np.median(want))
    return np.where(np.isfinite(gap), gap, np.inf)


def norm_readings(got, want, names) -> dict:
    """The three ways two vectors of leaf norms are held together, each
    with where it was read: ``rel`` the worst leaf's gap, ``rms_rel`` the
    root mean square of the leaves' gaps (steady from seed to seed where
    the worst leaf swings), ``total_rel`` the gap of the whole tree's
    norm."""
    gap = leaf_gaps(got, want)
    i = int(np.argmax(gap))
    total = lambda v: math.sqrt(float(np.sum(np.square(v, dtype=np.float64))))
    return {"rel": (float(gap[i]), names[i]),
            "rms_rel": (float(np.sqrt(np.mean(gap ** 2))),
                        f"{len(gap)} leaves"),
            "total_rel": (abs(total(got) - total(want)) / total(want),
                          "whole tree")}


def loss_gap(got, want):
    """(largest relative gap of a step's loss, the step, from 1)."""
    if len(got) != len(want):
        raise ValueError(f"{len(got)} losses against {len(want)}")
    gaps = [abs(a - b) / abs(b) if math.isfinite(a) else math.inf
            for a, b in zip(got, want)]
    i = max(range(len(gaps)), key=gaps.__getitem__)
    return gaps[i], i + 1


def readings(program: dict, reference: dict) -> dict:
    """Every number the comparison can hold to a limit, as
    ``name -> (value, where)``: ``loss_rel``, and for ``grad_norm`` and
    ``change_norm`` the three of ``norm_readings``."""
    gap, step = loss_gap(program["loss"], reference["loss"])
    out = {"loss_rel": (gap, f"step {step}")}
    for key in ("grad_norm", "change_norm"):
        for stat, reading in norm_readings(
                program[key], reference[key],
                reference["leaf_names"]).items():
            out[f"{key}_{stat}"] = reading
    return out


def compare(program: dict, reference: dict, limits: dict) -> list:
    """One row per number that ``limits`` names among ``readings``: name,
    value, limit, where it was read, and whether it holds. A limit with
    another name belongs to another check and is left to it."""
    return [{"check": name, "value": value, "limit": limits[name],
             "where": where, "ok": bool(value <= limits[name])}
            for name, (value, where) in readings(program, reference).items()
            if name in limits]
