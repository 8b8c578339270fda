"""Host time per round of the service calls that do no device work:
``begin_round``, ``model_snapshot`` and the client's
``deserialize_pytree`` (the benchmark's own spans, by the host clock)."""

SPANS = ("begin_round", "model_snapshot", "deserialize")


def read(ctx):
    if not ctx["rounds"]:
        return None
    return 1e3 * sum(ctx["spans_s"].get(s, 0.0) for s in SPANS) / ctx["rounds"]
