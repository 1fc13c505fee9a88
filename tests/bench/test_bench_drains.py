"""The serving engine's drains read from its spans in a profiler trace:
``bench/drains.py`` on a synthesized trace with known answers, and on
traces that the engine itself recorded on the CPU."""

import types

import numpy as np
import pytest

from benchtiny import SEED

from bench import drains, tracereduce  # noqa: E402

# Times in ns. The window is [1000, 20000). Drain 1 packs before it and
# drain 4 resolves after it, so both are dropped; drains 2 and 3 are whole.
FLUSHER = [
    ("serve.pack", 500, 700, {"drain": 1, "n_requests": 9}),
    ("serve.dispatch", 700, 800, {"drain": 1}),
    ("serve.wait", 1000, 1500, {"drain": 2}),
    ("serve.hold", 1500, 2000, {"drain": 2}),
    ("serve.pack", 2000, 2300, {"drain": 2, "n_requests": 2, "rows": 5}),
    ("serve.dispatch", 2300, 2400, {"drain": 2, "n_slabs": 2}),
    ("serve.wait", 2400, 2600, {"drain": 3}),
    ("serve.hold", 2600, 4000, {"drain": 3}),
    ("serve.pack", 4000, 4200, {"drain": 3, "n_requests": 4, "rows": 9}),
    ("serve.dispatch", 4200, 4300, {"drain": 3, "n_slabs": 1}),
    ("serve.pack", 18000, 18200, {"drain": 4, "n_requests": 1}),
    ("serve.dispatch", 18200, 18300, {"drain": 4}),
]
RUNNER = [
    ("serve.device", 800, 1200, {"drain": 1}),
    ("serve.gather", 1200, 1400, {"drain": 1}),
    ("serve.assemble", 1400, 1450, {"drain": 1}),
    ("serve.resolve", 1450, 1500, {"drain": 1}),
    ("serve.account", 1500, 1600, {"drain": 1}),
    ("serve.device", 2500, 2700, {"drain": 2, "rows": 8}),
    ("serve.device", 2700, 3000, {"drain": 2, "rows": 8}),
    ("serve.gather", 3000, 3600, {"drain": 2}),
    ("serve.assemble", 3600, 3700, {"drain": 2}),
    ("serve.resolve", 3700, 3900, {"drain": 2}),
    ("serve.account", 3900, 4500, {"drain": 2}),
    ("serve.device", 4600, 4900, {"drain": 3}),
    ("serve.gather", 4900, 5200, {"drain": 3}),
    ("serve.assemble", 5200, 5300, {"drain": 3}),
    ("serve.resolve", 5300, 5600, {"drain": 3}),
    ("serve.account", 5600, 5800, {"drain": 3}),
    ("serve.device", 18400, 18600, {"drain": 4}),
    ("serve.gather", 18600, 19000, {"drain": 4}),
    ("serve.assemble", 19000, 19100, {"drain": 4}),
    ("serve.resolve", 19100, 20500, {"drain": 4}),
    ("serve.account", 20500, 20600, {"drain": 4}),
]
OPS = [(2500, 3500), (4600, 5200), (18400, 18900)]   # chip 0's ops
IDLE = 1500 + 1100 + 13200 + 1100                    # their holes, in ns


def _text():
    names, stats = {}, {}

    def meta(table, name):
        return table.setdefault(name, len(table) + 1)

    def events(rows):
        out = []
        for name, s, e, args in rows:
            st = "".join(f" stats {{ metadata_id: {meta(stats, k)} "
                         f"int64_value: {v} }}" for k, v in args.items())
            out.append(f"events {{ metadata_id: {meta(names, name)} "
                       f"offset_ps: {s * 1000} duration_ps: "
                       f"{(e - s) * 1000}{st} }}")
        return "\n".join(out)

    host = "\n".join(
        f'lines {{ id: {i + 1} name: "python" timestamp_ns: 0\n'
        f'{events(rows)} }}' for i, rows in enumerate(
            [[("bench.window", 1000, 20000, {})], FLUSHER, RUNNER]))
    ops = "\n".join(f"events {{ metadata_id: 1 offset_ps: {s * 1000} "
                    f"duration_ps: {(e - s) * 1000} }}" for s, e in OPS)
    ev_meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in names.items())
    st_meta = "\n".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in stats.items())
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
{ops} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.1" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
{host}
{ev_meta}
{st_meta}
}}
"""


@pytest.fixture(scope="module")
def synthesized():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(_text())
    return profile, tracereduce.parse(profile)


def test_spans_keep_their_thread_line_and_stats(synthesized):
    profile, _ = synthesized
    spans = drains.program_spans(profile)
    assert len(spans) == len(FLUSHER) + len(RUNNER)   # not bench.window
    lines = {sp[2]: set() for sp in spans}
    for _, _, name, line, _ in spans:
        lines[name].add(line)
    assert lines["serve.pack"] == {"/host:CPU#1"}
    assert lines["serve.device"] == {"/host:CPU#2"}
    pack = [sp for sp in spans if sp[2] == "serve.pack"][1]
    assert pack[:2] == (2000, 2300)
    assert pack[4] == {"drain": 2, "n_requests": 2, "rows": 5}


def test_drains_cut_by_the_window_are_dropped(synthesized):
    profile, tr = synthesized
    got = drains.by_drain(drains.program_spans(profile), tr.window)
    assert list(got) == [2, 3]
    assert len(got[2]) == 10 and len(got[3]) == 9
    assert {sp[3] for sp in got[2]} == {"/host:CPU#1", "/host:CPU#2"}


def test_the_four_readings(synthesized):
    profile, tr = synthesized
    got = drains.phases(drains.by_drain(drains.program_spans(profile),
                                        tr.window))
    assert got == pytest.approx({
        "serve.hold_ms_per_drain": (500 + 1400) / 2 * 1e-6,
        # (last device end - pack start), weighted by 2 and 4 requests
        "serve.launch_ms": (2 * 1000 + 4 * 900) / 6 * 1e-6,
        # (resolve end - last device end)
        "serve.return_ms": (2 * 900 + 4 * 700) / 6 * 1e-6,
        "serve.requests_per_drain": 3.0})
    assert drains.phases({}) is None


def test_launch_parts_find_the_runner_busy_with_another_drain(synthesized):
    profile, tr = synthesized
    parts = drains.launch_parts(drains.by_drain(
        drains.program_spans(profile), tr.window))
    # drain 2 waits [2400, 2500) for an idle runner; drain 3 waits
    # [4300, 4600), 200 ns of it behind drain 2's account
    assert parts["runner_queue"] == pytest.approx((2 * 100 + 4 * 300) / 6
                                                  * 1e-6)
    assert parts["runner_queue_behind_finalize"] == pytest.approx(
        4 * 200 / 6 * 1e-6)
    assert parts["serve.device"] == pytest.approx((2 * 500 + 4 * 300) / 6
                                                  * 1e-6)


def test_idle_time_under_each_span(synthesized):
    profile, tr = synthesized
    under = drains.idle_under(tr, drains.program_spans(profile))
    assert under["serve.wait"] == pytest.approx((500 + 100) / IDLE)
    assert under["serve.hold"] == pytest.approx((500 + 500) / IDLE)
    # [4500, 4600), [5800, 18000) and [18300, 18400) are under no span
    assert under["none"] == pytest.approx((100 + 12200 + 100) / IDLE)


def test_idle_gaps_are_named_by_the_program_span_over_them(synthesized):
    _, tr = synthesized
    gaps = dict((round(dt * 1e9), name) for name, dt in
                tracereduce.summarize(tr)["idle_gaps"])
    assert gaps[1500] == "host:none/serve.hold"      # [1000, 2500)
    assert gaps[13200] == "host:none/none"           # [5200, 18400)


# ---- the engine's own spans, recorded on the CPU ---------------------------

def _engine_profile(tmp_path, start, waves=8):
    import jax
    from jax.profiler import ProfileData

    from repro.core import KernelSpec, oos
    from repro.data import kpca_dataset
    from repro.serve import KpcaEngine, KpcaServeConfig

    model = oos.fit_central(kpca_dataset(64, m=8, seed=0),
                            KernelSpec(kind="rbf"), n_components=2)
    eng = KpcaEngine(model, KpcaServeConfig(max_batch=16, min_bucket=8))
    rng = np.random.default_rng(0)
    if start:
        eng.start()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for w in range(waves):      # a wave of requests, then answers
                futs = [eng.submit(rng.normal(size=(1 + (w * 5 + i) % 20, 8))
                                   .astype(np.float32)) for i in range(5)]
                if not start:
                    eng.flush()
                for f in futs:
                    f.result(timeout=60)
            eng.close()
    finally:
        jax.profiler.stop_trace()
    prof = ProfileData.from_file(tracereduce.find_xplane(str(tmp_path)))
    return prof, eng.stats


@pytest.mark.parametrize("pipelined", [True, False])
def test_engine_drains_carry_one_id_across_threads(tmp_path, pipelined):
    profile, stats = _engine_profile(tmp_path, start=pipelined)
    spans = drains.program_spans(profile)
    window = next((int(e.start_ns), int(e.start_ns + e.duration_ns))
                  for p in profile.planes for line in p.lines
                  for e in line.events if e.name == "bench.window")
    got = drains.by_drain(spans, window)
    assert len(got) == stats.n_flushes > 1
    for group in got.values():
        names = [sp[2] for sp in group]
        for name in ("serve.pack", "serve.dispatch", "serve.gather",
                     "serve.assemble", "serve.resolve", "serve.account"):
            assert names.count(name) == 1, (name, names)
        assert names.count("serve.device") >= 1
        lines = {sp[2]: sp[3] for sp in group}
        if pipelined:    # the flusher cuts, the device runner finishes
            assert lines["serve.pack"] != lines["serve.device"]
            assert lines["serve.device"] == lines["serve.resolve"]
        else:            # flush() runs the whole drain on its caller
            assert len(set(lines.values())) == 1
    if pipelined:
        assert any(sp[2] == "serve.wait" for sp in spans)
    assert drains.phases(got)["serve.requests_per_drain"] == \
        stats.n_requests / stats.n_flushes


def _standin_parse(real):
    """``tracereduce.parse`` for the CPU, which has no device plane: the
    host's XLA op events stand in for one device's ops."""
    def parse(profile):
        planes = list(profile.planes)
        ops = [ev for p in planes if p.name == "/host:CPU"
               for line in p.lines if line.name.startswith("tf_XLA")
               for ev in line.events if "hlo_op" in dict(ev.stats)]
        dev = types.SimpleNamespace(name="/device:STANDIN:0", lines=[
            types.SimpleNamespace(name=tracereduce.OPS_LINE, events=ops)])
        return real(types.SimpleNamespace(planes=planes + [dev]))
    return parse


def test_measure_splits_a_traced_window(bench_json, home, monkeypatch):
    monkeypatch.setattr(tracereduce, "parse",
                        _standin_parse(tracereduce.parse))
    out = drains.measure(bench_json, "t.serve", SEED, 1.0, home=home)
    assert out["drains"] > 10
    for key in ("serve.hold_ms_per_drain", "serve.launch_ms",
                "serve.return_ms", "serve.requests_per_drain",
                "serve.queue_wait_ms", "due_to_answer_mean_ms",
                "remainder_ms"):
        assert np.isfinite(out[key]), key
    assert out["serve.launch_ms"] > 0 and out["serve.return_ms"] > 0
    assert 0 <= out["idle_under"]["serve.wait"] <= 1
    assert all(v <= lim for v, lim in out["checks"].values())
