"""The program's host spans (repro.telemetry): nesting, attributes, spans
closed by an exception, the bounded ring and one record per run."""
import pytest

from repro import telemetry


@pytest.fixture(autouse=True)
def fresh_record():
    telemetry.start_run()
    yield
    telemetry.start_run()


def by_name():
    return {s.name: s for s in telemetry.spans()}


def test_nesting_records_the_parent():
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
        with telemetry.span("sibling"):
            pass
    got = by_name()
    assert [s.name for s in telemetry.spans()] == ["inner", "sibling",
                                                   "outer"]
    assert got["outer"].parent is None
    assert got["inner"].parent == got["outer"].id
    assert got["sibling"].parent == got["outer"].id
    assert got["outer"].start_ns <= got["inner"].start_ns
    assert got["inner"].end_ns <= got["sibling"].start_ns
    assert got["sibling"].end_ns <= got["outer"].end_ns


def test_attributes_are_kept():
    with telemetry.span("chunk", round=4, rounds=2) as s:
        s.set(bytes=1234)
    (span,) = telemetry.spans()
    assert span.attrs == {"round": 4, "rounds": 2, "bytes": 1234}
    assert span.error is None
    assert s.seconds == span.seconds >= 0.0


def test_a_span_sums_its_direct_children_by_name():
    with telemetry.span("outer") as outer:
        for _ in range(2):
            with telemetry.span("lower"):
                with telemetry.span("compile"):
                    pass
        with telemetry.span("compile"):
            pass
    lower = [s.seconds for s in telemetry.spans() if s.name == "lower"]
    own = [s.seconds for s in telemetry.spans()
           if s.name == "compile" and s.parent == by_name()["outer"].id]
    assert outer.inner("lower") == pytest.approx(sum(lower))
    assert outer.inner("lower", "compile") == pytest.approx(sum(lower + own))
    assert outer.inner("execute") == 0.0


def test_an_exception_closes_and_records_the_span():
    class Stop(Exception):
        pass

    with pytest.raises(Stop):
        with telemetry.span("outer"):
            with telemetry.span("inner"):
                raise Stop()
    got = by_name()
    assert got["inner"].error == got["outer"].error == "Stop"
    assert got["inner"].parent == got["outer"].id
    # the stack unwound: a new span has no parent
    with telemetry.span("after"):
        pass
    assert by_name()["after"].parent is None


def test_the_ring_is_bounded():
    for i in range(telemetry.RING + 10):
        with telemetry.span("s", i=i):
            pass
    kept = telemetry.spans()
    assert len(kept) == telemetry.RING
    assert kept[-1].attrs["i"] == telemetry.RING + 9
    assert kept[0].attrs["i"] == 10


def test_a_new_run_starts_a_new_record():
    from repro.api import FederatedJob, TaskConfig
    with telemetry.span("before the job"):
        pass
    FederatedJob(task=TaskConfig(kind="tokens", arch="smollm-135m", sites=2,
                                 batch=2, seq=8, seed=0),
                 rounds=1, round_engine="scan").run()
    names = {s.name for s in telemetry.spans()}
    assert "before the job" not in names
    assert "repro.chunk" in names
    telemetry.start_run()
    assert telemetry.spans() == []
