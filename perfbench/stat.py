"""The percentile rule every latency metric uses."""


def pct(values, p: float) -> float:
    """Nearest-rank percentile of an unsorted sample (the rule of
    ``repro.obs.metrics.percentile``, copied so the yardstick stays here)."""
    xs = sorted(values)
    k = min(len(xs) - 1, max(0, int(round(p / 100 * (len(xs) - 1)))))
    return xs[k]
