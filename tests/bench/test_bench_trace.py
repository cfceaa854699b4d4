"""The trace reduction, on traces small enough to check by hand and on one
recorded on a TPU v5e."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench.trace import reduce_profile

DATA = Path(__file__).parent / "data"

# one chip; the window is [0, 100) us: ops at [10, 30) and [25, 40)
# (overlapping: busy 30 us), a Pallas kernel at [60, 70); host spans
# bench.pump over [0, 50) and bench.step over [45, 100)
SMALL = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 10000000 }
    events { metadata_id: 1 offset_ps: 150000000 duration_ps: 10000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.12" } }
  event_metadata { key: 2 value { id: 2 name: "copy.3" } }
  event_metadata { key: 3 value { id: 3 name: "fused_round_kernel" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 0 duration_ps: 50000000 }
    events { metadata_id: 3 offset_ps: 45000000 duration_ps: 55000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.pump" } }
  event_metadata { key: 3 value { id: 3 name: "bench.step" } }
}
"""


def test_small_trace_by_hand():
    tr = reduce_profile(ProfileData.from_text_proto(SMALL),
                        kernel_names=("fused_round",))
    assert tr.chips == 1
    assert tr.window_s == pytest.approx(100e-6)
    assert tr.busy_s == pytest.approx(40e-6)          # 30 + 10 us
    assert tr.idle_share == pytest.approx(0.6)
    # the op outside the window does not count; an op that overlaps an
    # earlier one counts its own part
    assert tr.op_s == pytest.approx({"fusion.12": 20e-6, "copy.3": 10e-6,
                                     "fused_round_kernel": 10e-6})
    assert tr.kernel_s == pytest.approx({"fused_round": 10e-6})
    # gaps: [70, 100) under bench.step, [40, 60) mostly under bench.pump
    # (10 us) against bench.step (15 us), [0, 10) under bench.pump
    assert [g[0] for g in tr.gaps] == ["bench.step", "bench.step",
                                       "bench.pump"]
    assert [g[1] for g in tr.gaps] == pytest.approx([30e-6, 20e-6, 10e-6])


def test_trace_without_window_or_device_is_refused():
    no_window = SMALL.replace('"bench.window"', '"other"')
    with pytest.raises(ValueError, match="bench.window"):
        reduce_profile(ProfileData.from_text_proto(no_window))
    no_device = SMALL.replace('"/device:TPU:0"', '"/host:other"')
    with pytest.raises(ValueError, match="device plane"):
        reduce_profile(ProfileData.from_text_proto(no_device))
