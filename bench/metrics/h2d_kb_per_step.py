"""Host-to-device bytes of the step operands (the ``h2d_bytes`` the
serving core counts and puts on each ``serve.dispatch`` span) per verdict
step, in KB of 1024 bytes."""

from bench import stages


def read(ctx):
    return stages.kb_per_step(ctx, "serve.dispatch", "h2d_bytes")
