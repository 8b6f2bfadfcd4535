"""Self time of the serving core's operand build (``serve.operands``:
stacking and padding the pending readings, placing them and the step's
scalar operands) per verdict step, in ms."""

from bench import stages


def read(ctx):
    return stages.stage_ms(ctx, "serve.operands", own=True)
