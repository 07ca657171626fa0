"""expert_load_peak (ratio, program counter): the busiest held expert's
picks, summed over the window's dispatches and layers, over the mean held
expert's, EngineStats.expert_picks_peak * experts held / expert_picks_held;
1.0 is an even load. None where the program keeps no such counter."""


def read(ctx):
    held = ctx.stats.get("expert_picks_held", 0)
    if not held:
        return None
    cfg = ctx.cell.config
    n = cfg.get("experts_held") or cfg["num_experts"]
    return ctx.stats["expert_picks_peak"] * n / held
