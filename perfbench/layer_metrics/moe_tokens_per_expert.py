"""(token, choice) pairs that landed on this chip's experts over the held
experts that received one, per decode step and layer, over the window: how
many rows share one read of an expert's weights. From the program's
counters ``serving/moe/expert_assignments`` and ``serving/moe/experts_hit``
(the ``serve_moe_route`` marks carry the same two numbers a step)."""

LAYER = "experts"
UNIT = "count"
BETTER = "higher"
MOVES = "itl_p99_ms"
SOURCE = "program_counter"
DRIVERS = ('serve_closed_loop_hf',)


def read(ctx):
    hit = ctx.counters.get("moe_experts_hit")
    landed = ctx.counters.get("moe_expert_assignments")
    if not hit or landed is None:
        return None
    return landed / hit
