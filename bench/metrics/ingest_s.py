"""Ingestion seconds per round: the program's ``ingest.route`` spans (a
scan's round output hash-routed to its partitions, ``partition._ScanRouter``)
less the ``ingest.source`` spans nested in them (the scan's own function),
summed over threads, over the window's rounds."""


def read(obs):
    if not obs.n_rounds or all(c != "ingest.route" for c, *_ in obs.spans):
        return None
    return obs.self_seconds("ingest.route", "ingest.source") / obs.n_rounds
