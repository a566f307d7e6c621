"""Step programs: program variants compiled or loaded from the cache
before the window (every first call of a jitted program in set-up,
``engine.stats["programs_built"]`` at the window's start)."""


def read(run):
    built = run.window["stats0"].get("programs_built")
    return None if built is None else float(built)
