"""Share of the tower rows run that served no request:
1 - tower inferences / (serve steps x miss budget), from the program's
counters and the static shapes."""


def read(run):
    steps = sum(d.steps for d in run.traced)
    if not steps:
        return None
    inferences = sum(int(d.acc["tower_inferences"]) for d in run.traced)
    return 1.0 - inferences / (steps * run.miss_budget)
