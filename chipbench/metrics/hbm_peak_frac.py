"""Peak device memory in use (``peak_bytes_in_use``) over the memory the
runtime can hand out (``bytes_limit``), read after the window."""


def read(ctx):
    mem = ctx["memory"] or {}
    if not mem.get("peak_bytes_in_use") or not mem.get("bytes_limit"):
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
