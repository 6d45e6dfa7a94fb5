"""Fusion: stages that fell back to the host (`fusion_stats()["fallbacks_total"]`)
plus programs built inside the window (the program's CompileCache misses and
JAX's own compile requests). All of them should read 0."""


def read(ctx):
    c = ctx["counters"]
    if "fallbacks_total" not in c:
        return None
    return float(c["fallbacks_total"] + c["program_cache_misses_in_window"]
                 + c["compiles_in_window"])
