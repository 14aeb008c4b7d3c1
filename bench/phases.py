"""Per-request times the program reports in each answer, read by the
per-layer metrics of the served path. Each reader returns None where no
answer carries the field: a program that does not report it."""
from bench.stats import quantile


def median_ms(values):
    d = [v * 1e3 for v in values]
    return quantile(d, 50) if d else None


def search_phase_ms(run, phase: str):
    """Median, over searched answers in the window, of the request's total
    time in the search phase ``phase`` (``report["search_phases_s"]``)."""
    return median_ms(
        r["response"]["report"]["search_phases_s"][phase]
        for r in run.window_records()
        if r["response"] is not None and r["response"]["status"] == "miss"
        and phase in r["response"]["report"].get("search_phases_s", {}))
