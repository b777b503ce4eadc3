"""The trace reducer on a small recorded trace: busy union, idle share,
kernel time by name, self time of nested operations, labelled gaps."""
from jax.profiler import ProfileData

from chipbench import trace as TR

# one host thread: the window (0-100 us) and two engine steps; one device
# line: a loop (12-22 us) holding a kernel (13-15 us), a second kernel
# (40-50 us) and an op that overlaps it (45-60 us)
XSPACE = '''
planes { id: 1 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 35000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "engine_step" } }
  event_metadata { key: 3 value { id: 3 name: "generator" } }
}
planes { id: 2 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 12000000 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 13000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 40000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 45000000 duration_ps: 15000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 5 offset_ps: 12000000 duration_ps: 60000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while = (s32[]) while()" } }
  event_metadata { key: 2 value { id: 2 name: "%int4_matmul.3 = f32[8,8] custom-call(s8[8,8] %act_quant.1)" } }
  event_metadata { key: 3 value { id: 3 name: "%int8_matmul = f32[8,8] custom-call()" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.7 = f32[8] fusion()" } }
  event_metadata { key: 5 value { id: 5 name: "jit_ef" } }
}
'''


def _view(tmp_path):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return TR.load(tmp_path)


def test_busy_and_idle(tmp_path):
    v = _view(tmp_path)
    assert abs(v.window_s - 100e-6) < 1e-12
    # union of [12,22] and [40,60] us
    assert abs(v.busy_s() - 30e-6) < 1e-12


def test_kernel_time_by_name(tmp_path):
    v = _view(tmp_path)
    assert abs(v.kernel_s(("int4_matmul",)) - 2e-6) < 1e-12
    assert abs(v.kernel_s(("int4_matmul", "int8_matmul")) - 12e-6) < 1e-12
    # an operand named act_quant does not make the matmul an act_quant
    assert v.kernel_s(("act_quant",)) == 0.0


def test_self_time_and_gaps(tmp_path):
    v = _view(tmp_path)
    top = dict((n, s) for n, s in v.top_ops())
    assert abs(top["while"] - 8e-6) < 1e-12
    assert abs(top["int4_matmul"] - 2e-6) < 1e-12
    gaps = v.idle_gaps()
    # 60-100 us under the generator span; 22-40 us, whose midpoint (31 us)
    # lies just past the engine step; 0-12 us, before the step
    assert [g[0] for g in gaps] == ["generator", "outside harness spans",
                                    "outside harness spans"]
    assert [round(g[1] * 1e6, 6) for g in gaps] == [40.0, 18.0, 12.0]
