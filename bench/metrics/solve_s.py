"""Planner solve seconds per round: the program's ``plan.solve`` spans
(``incremental.run_scenario``, the per-round solve on the scenario's
thread), over the window's rounds."""


def read(obs):
    if not obs.n_rounds or all(c != "plan.solve" for c, *_ in obs.spans):
        return None
    return obs.span_seconds("plan.solve") / obs.n_rounds
