"""Bursty open loop: gamma inter-arrival times with mean ``1 / rate_per_s``
and coefficient of variation ``cv`` (1 is Poisson; above 1 arrivals come in
bursts with lulls between), every request due in the window whether or not
earlier ones have finished.  ``in_flight`` as for ``poisson``."""
import numpy as np


def schedule(arr: dict, rng, seconds: float) -> dict:
    head = arr.get("in_flight", 0)
    shape = 1.0 / arr["cv"] ** 2
    mean = 1.0 / arr["rate_per_s"]
    times = np.zeros(0)
    while not len(times) or times[-1] < seconds:
        gaps = rng.gamma(shape, mean / shape, 256)
        times = np.concatenate([times, (times[-1] if len(times) else 0.0)
                                + np.cumsum(gaps)])
    return {"due": np.concatenate([np.zeros(head), times[times < seconds]]),
            "head": head}
