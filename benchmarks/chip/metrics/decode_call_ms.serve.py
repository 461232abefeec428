"""Mean host-clock time (ms) of a pure decode call of the serving engine:
``CompiledServingEngine.step()`` calls during which no request was
admitted, so each is one fused K-token decode program plus the host's
replay of its tokens. Timed from the benchmark's side over the whole
window; the sum spans many calls."""


def read(ctx):
    f = ctx["facts"]
    if not f.get("decode_calls"):
        return None
    return 1e3 * f["decode_call_s"] / f["decode_calls"]
