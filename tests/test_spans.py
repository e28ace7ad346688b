"""The span primitive (utils/telemetry.py) and the serving loop's lag
gauge (service/front_end.py).

- disarmed, ``span`` is one shared no-op: it records nothing, writes no
  file and opens no profiler annotation;
- armed, nesting gives parent indices (and so self time), the store is
  bounded and counts what it drops, an uncatalogued name raises, and the
  output file holds what was recorded;
- the anchor and the window's mark on the profile place wall-clock
  spans on the profiler's clock;
- the loop-lag ticker, against a loop held for 100 ms, reports the hold
  on the metrics registry and as a ``frontend.loop_stall`` span.
"""

import asyncio
import glob
import json
import os
import time

import pytest

from fluidframework_tpu.obs import tier_counters
from fluidframework_tpu.obs.metrics import get_registry
from fluidframework_tpu.utils import telemetry
from fluidframework_tpu.utils.telemetry import (record_span, sample, span,
                                                tracing_start, tracing_stop)


@pytest.fixture(autouse=True)
def _disarmed():
    yield
    if telemetry.tracing_armed():
        tracing_stop()


def _load(path):
    with open(path) as f:
        return json.load(f)


def test_disarmed_span_is_a_shared_noop(tmp_path, monkeypatch):
    import jax

    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: opened.append(name))
    assert not telemetry.tracing_armed()
    assert span("stage.poll") is span("log.flush")
    with span("stage.checkpoint"):
        with span("no.such.span"):  # names are checked only when armed
            sample("frontend.loop.lag_ms", 1.0)
            record_span("frontend.loop_stall", 0, 1)
    assert tracing_stop() is None
    assert opened == []
    assert os.listdir(tmp_path) == []


def test_armed_nesting_parents_and_self_time(tmp_path):
    from benchmark.program_trace import reduce_program

    c = tier_counters("stage")
    c.inc("stage.publish.records", 5)  # before the window: not counted
    tracing_start(str(tmp_path), "unit", counters=(c,))
    with span("stage.checkpoint"):
        with span("applier.flush"):
            time.sleep(0.002)
        with span("checkpoint.save"):
            with span("checkpoint.write_arrays"):
                time.sleep(0.002)
    with span("stage.poll"):
        pass
    c.inc("stage.publish.records", 3)
    sample("stage.deli_to_applied.ms", 12.5)
    doc = _load(tracing_stop())
    names = [s[0] for s in doc["spans"]]
    assert names == ["stage.checkpoint", "applier.flush", "checkpoint.save",
                     "checkpoint.write_arrays", "stage.poll"]
    assert [s[3] for s in doc["spans"]] == [-1, 0, 0, 2, -1]
    assert doc["counters"] == {"stage.publish.records": 3}
    assert [v for _, v in doc["samples"]["stage.deli_to_applied.ms"]] \
        == [12.5]
    ck, fl, sv, wa, _ = (s[2] for s in doc["spans"])
    r = reduce_program(doc)
    assert r["stage.checkpoint"]["self_ms"] == pytest.approx(
        (ck - fl - sv) / 1e6)
    assert r["checkpoint.save"]["self_ms"] == pytest.approx((sv - wa) / 1e6)
    assert ck >= fl + sv and fl >= 2e6


def test_the_store_is_bounded_and_counts_overflow(tmp_path, monkeypatch):
    monkeypatch.setattr(telemetry, "DEFAULT_SPAN_CAPACITY", 3)
    tracing_start(str(tmp_path), "unit")
    with span("stage.checkpoint"):
        for _ in range(4):
            with span("stage.poll"):
                pass
    record_span("frontend.loop_stall", 1, 2)
    doc = _load(tracing_stop())
    assert len(doc["spans"]) == 3 and doc["capacity"] == 3
    assert doc["overflow"] == 3
    assert all(s[2] >= 0 for s in doc["spans"])


def test_a_span_open_at_the_stop_is_cut_and_listed(tmp_path):
    tracing_start(str(tmp_path), "unit")
    with span("stage.checkpoint"):
        with span("stage.publish"):
            pass
        doc = _load(tracing_stop())
    assert doc["open"] == [0]
    ck = doc["spans"][0]
    assert 0 <= ck[2] <= doc["anchor"]["stop_wall_ns"] - ck[1]


def test_uncatalogued_names_raise_when_armed(tmp_path):
    tracing_start(str(tmp_path), "unit")
    with pytest.raises(ValueError, match="SPANS"):
        span("stage.pol")
    with pytest.raises(ValueError, match="SAMPLES"):
        sample("stage.lag", 1.0)
    with pytest.raises(RuntimeError):
        tracing_start(str(tmp_path), "again")


def test_the_output_file_round_trips(tmp_path):
    tracing_start(str(tmp_path / "out"), "core")
    anchor = telemetry._ARMED.anchor_wall_ns
    with span("frontend.backchannel"):
        with span("log.flush"):
            pass
    record_span("frontend.loop_stall", anchor + 5, 40_000_000)
    sample("frontend.loop.lag_ms", 40.0)
    held = [list(s) for s in telemetry._ARMED.spans]
    path = tracing_stop()
    assert path == str(tmp_path / "out" / f"spans-core-{os.getpid()}.json")
    doc = _load(path)
    assert doc["spans"] == held
    assert doc["role"] == "core" and doc["pid"] == os.getpid()
    assert doc["anchor"]["wall_ns"] == anchor
    assert doc["anchor"] == {"wall_ns": anchor,
                             "stop_wall_ns": doc["anchor"]["stop_wall_ns"]}
    assert doc["anchor"]["stop_wall_ns"] >= anchor
    assert doc["samples"]["frontend.loop.lag_ms"][0][1] == 40.0
    assert doc["overflow"] == 0 and doc["counters"] == {}
    assert doc["open"] == []
    assert glob.glob(str(tmp_path / "out" / "*.tmp")) == []


def test_anchor_places_wall_spans_on_the_profile(tmp_path):
    """With JAX loaded each span is also a profiler annotation, and
    arming marks the window's start on the profile; that mark less the
    file's anchor moves a span's wall start onto the profile's clock,
    where the annotation of the same span lies."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from benchmark.program_trace import wall_to_trace_ns, window_start_ns

    d = str(tmp_path)
    jax.profiler.start_trace(d)
    tracing_start(d, "stage")
    with span("stage.poll"):
        jnp.ones(8).sum().block_until_ready()
    time.sleep(0.005)
    with span("stage.drain"):
        time.sleep(0.002)
    doc = _load(tracing_stop())
    jax.profiler.stop_trace()
    offset = wall_to_trace_ns(doc, window_start_ns(d))
    assert offset is not None
    (pb,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    on_profile = {e.name: e.start_ns
                  for p in ProfileData.from_file(pb).planes
                  if p.name.startswith("/host:CPU")
                  for ln in p.lines for e in ln.events
                  if e.name in ("stage.poll", "stage.drain")}
    for name, start, *_ in doc["spans"]:
        assert abs(start + offset - on_profile[name]) < 200_000, name


def test_loop_lag_ticker_reports_a_held_loop(tmp_path):
    from fluidframework_tpu.service.front_end import loop_lag_ticker

    tracing_start(str(tmp_path), "core")

    async def main():
        task = asyncio.get_running_loop().create_task(loop_lag_ticker())
        await asyncio.sleep(0.05)
        asyncio.get_running_loop().call_soon(time.sleep, 0.1)
        await asyncio.sleep(0.15)
        task.cancel()

    asyncio.run(main())
    doc = _load(tracing_stop())
    count, q = get_registry().window_stats("frontend.loop.lag_ms",
                                           quantiles=(1.0,))
    assert count >= 5 and q[1.0] >= 90.0
    lags = [v for _, v in doc["samples"]["frontend.loop.lag_ms"]]
    assert max(lags) >= 90.0
    stalls = [s for s in doc["spans"] if s[0] == "frontend.loop_stall"]
    assert len(stalls) == 1 and stalls[0][2] >= 90e6
    assert "fluid_frontend_loop_lag_ms_count" in get_registry().scrape()


def test_the_long_tier_spans_nest_in_the_turn_and_counters_add_up(
        tmp_path, monkeypatch):
    """An applier stage with a long tier, under an armed trace: each
    promotion's span lies inside the turn's ``stage.checkpoint``, the
    tier's wave halves are there, and the window's tier counters are
    what the applier counted (the values STOPPED prints)."""
    from fluidframework_tpu import config
    from fluidframework_tpu.service.durable_log import DurableLog
    from fluidframework_tpu.service.load_gen import run_inproc
    from fluidframework_tpu.service.stage_runner import ApplierStage

    for name, value in (("applier_max_docs", 8), ("applier_max_slots", 16),
                        ("applier_ops_per_dispatch", 4),
                        ("applier_long_docs", 8),
                        ("applier_long_slots", 256)):
        monkeypatch.setattr(config.DEFAULT, name, value)
    log = DurableLog(str(tmp_path / "log"))
    run_inproc(n_docs=3, clients_per_doc=2, ops_per_client=24,
               batch_size=4, seed=5, log=log)
    log.flush()
    stage = ApplierStage(str(tmp_path / "log"), str(tmp_path / "state"))
    tracing_start(str(tmp_path / "trace"), "stage",
                  counters=(stage.counters,))
    while stage.run_once():
        pass
    doc = _load(tracing_stop())
    a = stage.applier
    assert a.promotions >= 1 and a.long_dispatches >= 1
    assert a.host_escalations == 0
    spans = doc["spans"]

    def ancestors(i):
        while spans[i][3] != -1:
            i = spans[i][3]
            yield spans[i][0]

    names = [s[0] for s in spans]
    promotes = [i for i, n in enumerate(names) if n == "applier.promote"]
    assert len(promotes) == a.promotions
    for i in promotes:
        assert "stage.checkpoint" in set(ancestors(i))
    assert names.count("applier.long_dispatch") == a.long_dispatches
    assert names.count("applier.long_stage_wave") == a.long_dispatches
    c = doc["counters"]
    assert c["applier.promotions"] == a.promotions
    assert c["applier.long.dispatches"] == a.long_dispatches
    assert c["applier.long.slots_peak"] == a.long_slots_peak > 16
    assert c["stage.checkpoint.long_bytes"] > 0
