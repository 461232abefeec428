"""Median host time (ms) of one ``EpochRunner.run_chunk`` call in the
window: what the host spends dispatching a step, read from the program's
own record of its ``EpochRunner.run_chunk`` spans (``repro.obs``). The
window's calls are the last ``facts["steps"]`` of them: nothing calls
``run_chunk`` after the window. None where the program keeps no such
record."""
import statistics

SPAN = "EpochRunner.run_chunk"


def read(ctx):
    try:
        from repro import obs
    except ImportError:
        return None
    steps = ctx["facts"].get("steps")
    calls = obs.recent(SPAN)
    if not steps or len(calls) < steps:
        return None
    return 1e3 * statistics.median(calls[-steps:])
