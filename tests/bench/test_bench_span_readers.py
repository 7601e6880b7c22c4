"""The readers of the plan, ingestion and data-plane spans, on made-up
spans, and nothing read from a program that records none of them."""
import pytest

from bench.observe import Observed, load_reader

# two window rounds, two workers; round 2's plan stretch [10, 11) holds its
# solve and a routed scan, round 3's [14, 16) a solve alone. Device calls
# run on the scenario's thread inside the routing and on the workers
# inside compute; one kernel was traced in round 3.
SPANS = [
    ("plan", 2, 10.0, 1.0, "main"), ("plan", 3, 14.0, 2.0, "main"),
    ("plan.solve", 2, 10.0, 0.5, "main"), ("plan.solve", 3, 14.0, 1.5, "main"),
    ("plan.prune", 2, 10.5, 0.5, "main"),
    ("ingest.route", 2, 10.5, 0.4, "main"),
    ("ingest.source", 2, 10.5, 0.25, "main"),
    ("dp.pid", 2, 10.8, 0.05, "main"),
    ("round", 2, 11.0, 3.0, "main"), ("round", 3, 16.0, 4.0, "main"),
    ("compute", 2, 11.0, 2.0, "w0"), ("dp.probe", 2, 11.5, 0.125, "w0"),
    ("compute", 3, 16.0, 1.0, "w1"), ("dp.encode_w", 3, 16.0, 0.25, "w1"),
    ("dp.map_mul", 3, 16.5, 0.075, "w1"),
    ("jit.trace", 3, 16.5, 0.0, "w1"),
]
NEW = ("solve_s", "ingest_s", "source_s", "dp_host_s", "dp_calls",
       "dp_traces")


def _obs(spans=SPANS, rounds=None):
    return Observed(
        rounds={2: (10.0, 14.0), 3: (14.0, 20.0)} if rounds is None
        else rounds, spans=spans, counters={}, n_workers=2,
        window=(10.0, 20.0))


@pytest.mark.parametrize("name,want", [
    ("solve_s", (0.5 + 1.5) / 2),
    ("ingest_s", (0.4 - 0.25) / 2),
    ("source_s", 0.25 / 2),
    ("dp_host_s", (0.05 + 0.125 + 0.25 + 0.075) / 2),
    ("dp_calls", 4 / 2),
    ("dp_traces", 1.0),
])
def test_span_and_counter_readers(name, want):
    assert load_reader(name)(_obs()) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_input(name):
    read = load_reader(name)
    # a program without these spans, and a window without rounds
    older = [s for s in SPANS if s[0] in ("round", "compute")]
    assert read(_obs(spans=older)) is None
    assert read(_obs(rounds={})) is None
    assert read(_obs(spans=[])) is None


@pytest.mark.parametrize("name", ["dp_host_s", "dp_calls", "dp_traces"])
def test_data_plane_readers_read_zero_on_the_host_data_plane(name):
    """With the plan spans recorded and no device call (the numpy data
    plane), the data plane made no calls and traced nothing."""
    host = [s for s in SPANS if not s[0].startswith(("dp.", "jit."))]
    assert load_reader(name)(_obs(spans=host)) == 0.0


def test_ingest_excludes_only_sources_of_its_own_thread():
    other = SPANS + [("ingest.source", 2, 10.6, 0.1, "w0")]
    assert load_reader("ingest_s")(_obs(spans=other)) == pytest.approx(
        (0.4 - 0.25) / 2)
    assert load_reader("source_s")(_obs(spans=other)) == pytest.approx(
        (0.25 + 0.1) / 2)
