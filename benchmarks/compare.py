"""The comparison that decides ``correct`` for factor tables.

For each table the cell's configuration lists under ``outputs``:

- ``<name>.fro``: ``||got - ref||_F / ||ref||_F`` over the whole table;
- ``<name>.rowmax``: the worst well-determined row, ``||got_r - ref_r||`` over
  the larger of ``||ref_r||`` and the median row norm of ``ref``. A row is well
  determined when its entity has at least ``rowmax_min_degree`` observations
  (4 x rank in the configurations): rows with about ``rank`` observations have
  ill-conditioned normal equations, their worst reads 0.10-0.28 from seed to
  seed under bf16 rounding alone (PR 25), and no limit holds on that.

A table that is missing, of another shape or not finite reads ``inf``.
Each number has its limit in the configuration's ``limits``; a number
without a limit there is an error, not a pass.
"""

from __future__ import annotations

import math

import numpy as np


def row_errors(got, ref):
    ref_rows = np.linalg.norm(ref, axis=1)
    floor = np.maximum(ref_rows, np.median(ref_rows))
    return np.linalg.norm(np.asarray(got, np.float64) - ref, axis=1) / floor


def table_numbers(got, ref, degree=None, min_degree: int = 0) -> dict:
    ref = np.asarray(ref, np.float64)
    if got is None or np.shape(got) != ref.shape or not np.isfinite(got).all():
        return {"fro": math.inf, "rowmax": math.inf}
    diff_rows = np.linalg.norm(np.asarray(got, np.float64) - ref, axis=1)
    ref_rows = np.linalg.norm(ref, axis=1)
    err = diff_rows / np.maximum(ref_rows, np.median(ref_rows))
    if degree is not None:
        err = err[np.asarray(degree) >= min_degree]
    return {
        "fro": float(np.linalg.norm(diff_rows) / np.linalg.norm(ref_rows)),
        "rowmax": float(err.max()) if len(err) else 0.0,
    }


def compare(got: dict, ref: dict, limits: dict, degrees: dict, min_degree: int):
    """``(correct, compared)``: ``compared`` maps each number's name to
    ``{"value", "limit"}``, in the order of ``ref``."""
    compared = {}
    for name, ref_table in ref.items():
        numbers = table_numbers(got.get(name), ref_table, degrees[name], min_degree)
        for kind, value in numbers.items():
            key = f"{name}.{kind}"
            if key not in limits:
                raise KeyError(f"the configuration sets no limit for {key}")
            compared[key] = {"value": value, "limit": float(limits[key])}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    return correct, compared


def worst_of(per_call: list) -> dict:
    """Fold the numbers of several calls into the worst of each."""
    out = {}
    for compared in per_call:
        for key, c in compared.items():
            if key not in out or not c["value"] <= out[key]["value"]:
                out[key] = c
    return out


def l2_normalize_rows(f):
    f = np.asarray(f, np.float32)
    n = np.linalg.norm(f, axis=1, keepdims=True)
    return np.where(n > 0, f / np.where(n > 0, n, 1), 0.0).astype(np.float32)
