"""The benchmark's trace reduction on synthesized traces with known answers,
and on a trace recorded on the CPU, whose host op events stand in for a
device plane."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import tracereduce  # noqa: E402
from benchtiny import standin_trace  # noqa: E402

# Two chips. Times in ns from the plane line's timestamp_ns (1000):
#   TPU:0 ops  fusion.1 [1000, 3000)  collective-permute.2 [2000, 4000)
#              fusion.1 [6000, 7000)
#   TPU:1 ops  fusion.1 [1000, 2000)  all-reduce.5 [8000, 9000)
#   modules    jit__proj [1000, 4000) on TPU:0, [1000, 2000) on TPU:1
#   host       bench.window [0, 10000), bench.fit.setup [4000, 5500),
#              bench.fit.admm [5500, 10000), PjitFunction [100, 900)
TEXT = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "collective-permute.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__proj(123)" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 7000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "all-reduce.5" } }
  event_metadata { key: 3 value { id: 3 name: "jit__proj(123)" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1500000 }
    events { metadata_id: 3 offset_ps: 5500000 duration_ps: 4500000 }
    events { metadata_id: 4 offset_ps: 100000 duration_ps: 800000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.fit.setup" } }
  event_metadata { key: 3 value { id: 3 name: "bench.fit.admm" } }
  event_metadata { key: 4 value { id: 4 name: "PjitFunction" } }
}
"""


@pytest.fixture(scope="module")
def synthesized():
    from jax.profiler import ProfileData
    return tracereduce.parse(ProfileData.from_text_proto(TEXT))


def test_parse_finds_window_devices_and_host_spans(synthesized):
    tr = synthesized
    assert tr.window == (0, 10000)
    assert [d.name for d in tr.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert [s[2] for s in tr.host_spans] == ["bench.fit.setup",
                                             "bench.fit.admm"]


def test_busy_is_the_union_of_op_intervals(synthesized):
    s = tracereduce.summarize(synthesized)
    # TPU:0 [1000, 4000) + [6000, 7000) = 4000 ns; TPU:1 1000 + 1000.
    assert s["busy_s_per_device"] == pytest.approx([4000e-9, 2000e-9])
    assert s["busy_s"] == pytest.approx(3000e-9)
    assert s["window_s"] == pytest.approx(10000e-9)
    assert s["n_devices"] == 2


def test_collective_time_is_the_mean_over_devices(synthesized):
    s = tracereduce.summarize(synthesized)
    assert s["collective_s"] == pytest.approx((2000 + 1000) / 2 * 1e-9)


def test_top_ops_are_summed_per_name_and_averaged(synthesized):
    ops = dict(tracereduce.summarize(synthesized)["device_ops"])
    assert ops["fusion.1"] == pytest.approx((3000 + 1000) / 2 * 1e-9)
    assert ops["collective-permute.2"] == pytest.approx(1000e-9)
    assert ops["all-reduce.5"] == pytest.approx(500e-9)


def test_idle_gaps_are_named_by_the_host_span_over_them(synthesized):
    gaps = tracereduce.summarize(synthesized)["idle_gaps"]
    # TPU:0 idle: [0, 1000) under PjitFunction [100, 900) only,
    # [4000, 6000) setup/admm (mid 5000: setup), [7000, 10000) admm;
    # longest first.
    assert gaps == [["host:bench.fit.admm/none", pytest.approx(3000e-9)],
                    ["host:bench.fit.setup/none", pytest.approx(2000e-9)],
                    ["host:none/PjitFunction", pytest.approx(1000e-9)]]


def test_module_seconds_match_by_name(synthesized):
    assert tracereduce.module_seconds(synthesized, r"_proj") == \
        pytest.approx((3000 + 1000) / 2 * 1e-9)
    assert tracereduce.module_seconds(synthesized, r"nothing") is None


def test_intervals_are_clipped_to_the_window():
    dev = tracereduce.Device("/device:TPU:0",
                             [(0, 50, "a"), (40, 120, "b")], [])
    assert tracereduce.busy_ns(dev, (20, 100)) == 80
    assert tracereduce.gaps(dev, (10, 130)) == [(120, 130)]


def test_a_trace_without_window_is_refused():
    from jax.profiler import ProfileData
    text = TEXT.replace('"bench.window"', '"something.else"')
    with pytest.raises(RuntimeError, match="bench.window"):
        tracereduce.parse(ProfileData.from_text_proto(text))


def test_a_trace_without_device_plane_is_refused():
    from jax.profiler import ProfileData
    host = TEXT[TEXT.index('  id: 3 name: "/host:CPU"') - len("planes {\n"):]
    with pytest.raises(RuntimeError, match="no device plane"):
        tracereduce.parse(ProfileData.from_text_proto(host))


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(3):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    # the CPU has no device plane: the refusal, then the tests' stand-in
    with pytest.raises(RuntimeError, match="no device plane"):
        tracereduce.load(str(tmp_path))
    s = tracereduce.summarize(standin_trace(str(tmp_path)))
    assert s["n_ops"] > 0
    assert 0 < s["busy_s"] <= s["window_s"]
