"""Device-to-host bytes of the step outputs (the ``d2h_bytes`` the serving
core counts and puts on each ``serve.unpack`` span) per verdict step, in KB
of 1024 bytes."""

from bench import stages


def read(ctx):
    return stages.kb_per_step(ctx, "serve.unpack", "d2h_bytes")
