"""Mean device time (ms) of one admission's prefill program in the traced
serving window: the device time of the engine's prefill programs (the XLA
modules named in ``PREFILL_MODULES``) over the admissions made while the
trace ran."""

PREFILL_MODULES = ("jit__lambda",)


def read(ctx):
    tr, f = ctx["trace"], ctx["facts"]
    if not tr or not f.get("prefills_traced"):
        return None
    seconds = sum(v for k, v in tr["module_s"].items()
                  if k.startswith(PREFILL_MODULES))
    if not seconds:
        return None
    return 1e3 * seconds / f["prefills_traced"]
