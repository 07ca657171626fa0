"""expert_held_share (%, program counter): share of the window's routed
picks of live tokens that land on the experts this chip holds,
100 * EngineStats.expert_picks_held / expert_picks. None where the program
keeps no such counter."""


def read(ctx):
    picks = ctx.stats.get("expert_picks", 0)
    return 100.0 * ctx.stats["expert_picks_held"] / picks if picks else None
