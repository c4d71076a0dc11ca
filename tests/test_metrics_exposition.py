"""Strict Prometheus text-exposition checks for ``ServeMetrics``.

``prometheus_text()`` is scraped by real collectors, whose parsers are
strict: every sample family must carry exactly one ``# HELP`` and one
``# TYPE`` line *before* its first sample, sample lines must match the
exposition grammar, label values must be quoted/escaped, and no
(name, labels) pair may repeat.  This module parses the full output
against that grammar -- on a metrics object pushed through request,
streaming, and recovery activity so every family has live samples.
"""

import re

import pytest

from repro.serve.metrics import ServeMetrics
from repro.serve.scheduler import Priority

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_SAMPLE = re.compile(
    rf"^({_NAME})(?:\{{((?:{_NAME}=\"[^\"\\\n]*\",?)*)\}})? (-?[0-9.e+-]+|NaN|[+-]Inf)$"
)
_HELP = re.compile(rf"^# HELP ({_NAME}) \S.*$")
_TYPE = re.compile(rf"^# TYPE ({_NAME}) (counter|gauge|histogram|summary|untyped)$")


def _populated_metrics():
    """A metrics object with activity in every family."""
    m = ServeMetrics()

    class _Req:
        uid = 0
        priority = Priority.STANDARD
        tenant = "default"
        latency_s = 0.012
        status = "completed"
        route = "lanes"
        tier = "full"

    m.inc("submitted")
    m.inc("completed")
    m.inc("rejected")
    m.record_finish(_Req(), now=0.0)
    for k in ("sessions_opened", "sessions_closed", "sessions_evicted",
              "sessions_restored", "session_chunks", "session_readouts"):
        m.inc(k)
    for k in ("recoveries_warm", "recoveries_cold", "tick_retries",
              "slow_ticks", "quarantined_lanes", "quarantine_restarts",
              "requests_resubmitted", "journal_records_replayed"):
        m.inc(k)
    m.recovering = 1
    m.recovery_s = 0.25
    m.record_tick(8, 0.003, queue_depth=1, active=2, n_lanes=4, now=0.0, launch_s=0.001)
    return m


def _parse(text):
    """Parse exposition text; returns (families, samples) or asserts."""
    helps, types, samples = {}, {}, []
    seen_sample_of = set()
    for i, line in enumerate(text.splitlines()):
        if not line.strip():
            continue
        if line.startswith("# HELP "):
            match = _HELP.match(line)
            assert match, f"line {i}: malformed HELP: {line!r}"
            name = match.group(1)
            assert name not in helps, f"duplicate HELP for {name}"
            assert name not in seen_sample_of, f"HELP for {name} after samples"
            helps[name] = line
        elif line.startswith("# TYPE "):
            match = _TYPE.match(line)
            assert match, f"line {i}: malformed TYPE: {line!r}"
            name = match.group(1)
            assert name not in types, f"duplicate TYPE for {name}"
            assert name not in seen_sample_of, f"TYPE for {name} after samples"
            types[name] = match.group(2)
        elif line.startswith("#"):
            pytest.fail(f"line {i}: unknown comment directive: {line!r}")
        else:
            match = _SAMPLE.match(line)
            assert match, f"line {i}: malformed sample: {line!r}"
            name, labels, value = match.groups()
            float(value)  # parses as a number
            samples.append((name, labels or "", value))
            seen_sample_of.add(name)
    return helps, types, samples


def test_every_family_has_help_and_type_before_samples():
    text = _populated_metrics().prometheus_text()
    helps, types, samples = _parse(text)
    for name, _, _ in samples:
        assert name in types, f"family {name} has samples but no # TYPE"
        assert name in helps, f"family {name} has samples but no # HELP"


def test_no_duplicate_name_label_pairs():
    _, _, samples = _parse(_populated_metrics().prometheus_text())
    keys = [(n, l) for n, l, _ in samples]
    assert len(keys) == len(set(keys)), "duplicate (name, labels) sample"


def test_recovery_and_quarantine_families_are_present_and_typed():
    helps, types, samples = _parse(_populated_metrics().prometheus_text())
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert types["neura_recovering"] == "gauge"
    assert by_name["neura_recovering"] == [("", "1")]
    assert types["neura_recovery_total"] == "counter"
    kinds = dict(by_name["neura_recovery_total"])
    assert kinds == {'kind="warm"': "1", 'kind="cold"': "1"}
    assert types["neura_recovery_seconds_total"] == "counter"
    assert float(by_name["neura_recovery_seconds_total"][0][1]) == 0.25
    events = dict(by_name["neura_recovery_events_total"])
    for ev in ("tick_retries", "slow_ticks", "requests_resubmitted",
               "journal_records_replayed"):
        assert events[f'event="{ev}"'] == "1"
    assert types["neura_quarantine_lanes_total"] == "counter"
    assert types["neura_quarantine_restarts_total"] == "counter"


def test_preexisting_families_kept_their_names_and_gained_metadata():
    # the PR-4/PR-8 dashboards scrape these exact names; adding HELP/TYPE
    # must not have renamed or dropped any of them
    helps, types, samples = _parse(_populated_metrics().prometheus_text())
    names = {n for n, _, _ in samples}
    for family in (
        "neura_requests_total",
        "neura_scheduler_events_total",
        "neura_route_requests_total",
        "neura_request_latency_seconds",
        "neura_stream_sessions",
        "neura_stream_events_total",
        "neura_ticks_total",
    ):
        assert family in types and family in helps
        assert family in names, f"{family} lost its samples"


def test_tick_launch_and_readback_families_split_the_tick_seconds():
    helps, types, samples = _parse(_populated_metrics().prometheus_text())
    value = {name: float(v) for name, labels, v in samples if not labels}
    for family in ("neura_tick_launch_seconds_total", "neura_tick_readback_seconds_total"):
        assert types[family] == "counter" and family in helps
    assert value["neura_tick_launch_seconds_total"] == 0.001
    assert value["neura_tick_readback_seconds_total"] == 0.002
    assert value["neura_tick_seconds_total"] == 0.003
