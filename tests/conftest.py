"""Shared test helpers."""
import os
import sys

import numpy as np

from repro.kernels import ref

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


def subproc_env() -> dict:
    """Env for subprocess tests: repo src on the path, CPU pinned (a libtpu
    is present in some images and every fresh process would otherwise burn
    ~2 min failing TPU init before falling back)."""
    return {"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin",
            "HOME": os.environ.get("HOME", "/root"), "JAX_PLATFORMS": "cpu"}


def assert_order_close(got, want, x, qt, spec, scale=None, zero=None,
                       task_ids=None):
    """|got − want| within the f32 summation-order bound (ref.ORDER_ULPS)."""
    tol = ref.f32_order_tolerance(
        x, qt.qw, qt.scale if scale is None else scale,
        qt.zero if zero is None else zero, qt.shape, spec, task_ids)
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert np.all(err <= np.asarray(tol)), float(np.max(err - tol))
