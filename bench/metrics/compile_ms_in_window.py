"""Host time JAX spent compiling programs, or loading them from its
persistent cache, inside the window (the program's ``jax.compile`` spans;
0 when none).  A program without request spans (``stream.queued``) has
no compile spans either, and reads nothing."""


def read(run):
    if not any(s[0] == "stream.queued" for s in run.spans):
        return None
    return sum(b - a for _, a, b, _ in run.window_spans("jax.compile")) * 1e3
