"""Planner time per round: from the previous round's last commit to the
start of this round's engine ``round`` span (the per-round solve, the
refresh view and the partition pruner all run there, on the scenario's
thread)."""


def read(obs):
    spans = obs.round_spans()
    gaps = [spans[r][0] - obs.rounds[r][0] for r in obs.rounds if r in spans]
    return sum(gaps) / len(gaps) if gaps else None
