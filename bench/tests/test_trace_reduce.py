"""bench/trace_reduce.py on a small trace recorded on four v5e chips: a
sharded matmul, an all-reduce and an all-gather, three steps apart."""
import os

import pytest

from bench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "psum_4chip.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_file(TRACE)


def test_busy_and_collective_time_per_chip(reduced):
    devs = reduced["devices"]
    assert sorted(devs) == [f"/device:TPU:{i}" for i in range(4)]
    for d in devs.values():
        assert d["n_ops"] == 24
        assert 0 < d["collective_s"] < d["busy_s"] < reduced["window_s"]
        # the collectives here run with no compute beside them
        assert d["exposed_collective_s"] == pytest.approx(d["collective_s"])


def test_per_layer_readers_take_the_trace(reduced):
    import importlib.util
    metrics = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                           "metrics")

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(metrics, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.compute

    planes = list(reduced["devices"].values())
    rec = {"trace": {"planes": planes},
           "busy_s": sum(p["busy_s"] for p in planes) / 4,
           "traced_window_s": reduced["window_s"]}
    idle = reader("device_idle_pct")(rec)
    exposed = reader("collective_exposed_pct")(rec)
    assert 99.0 < idle < 100.0 and 0.0 < exposed < 100.0 - idle
    assert reader("device_idle_pct")({"trace": {"planes": []}}) is None


def test_breakdown_names_ops_and_idle_gaps(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert names[0] == "psum_invariant.14"
    assert {"all-gather.5", "fusion"} <= set(names)
    secs = [s for _, s in reduced["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the host slept between the three steps
    assert reduced["idle_gaps"][0][0] == "$time sleep"
    assert len(reduced["idle_gaps"]) == tr.TOP


def test_parse_op_reads_name_and_opcode():
    assert tr.parse_op("%psum.1 = f32[8]{0} all-reduce(f32[8]{0} %x), "
                       "replica_groups={{0,1}}") == ("psum.1", "all-reduce")
    assert tr.parse_op("%copy-start = (f32[2]{0:S(1)}, u32[]{:S(2)}) "
                       "copy-start(f32[2]{0} %p)") == ("copy-start",
                                                       "copy-start")
    assert tr.is_collective("psum.1", "all-reduce")
    assert tr.is_collective("all-gather-start.3", "all-gather-start")
    assert not tr.is_collective("fusion.2", "fusion")


def test_interval_arithmetic():
    u = tr.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert u == [(0, 3), (5, 9)]
    assert tr.measure(u) == 7
    assert tr.minus(u, tr.union([(2, 6)])) == 2 + 3
    assert tr.minus(u, []) == 7 and tr.minus([], u) == 0
    assert tr.clip(u, 1, 6) == [(1, 3), (5, 6)]


SYNTHETIC = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 8000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 3 offset_ps: 3000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%while.1 = (f32[]) while(f32[] %p), body=%b" } }
  event_metadata { key: 2 value { id: 2
    name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3
    name: "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "main"
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 6500000 duration_ps: 3500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench_window" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(step)" } }
}
"""


def test_control_flow_ops_are_not_busy_time_and_the_window_is_marked():
    from jax.profiler import ProfileData
    red = tr.reduce_xspace(ProfileData.from_text_proto(SYNTHETIC))
    assert red["window_s"] == pytest.approx(10e-6)
    dev = red["devices"]["/device:TPU:0"]
    # the while op spans 1-9 us; its body ran 1-6 us
    assert dev["busy_s"] == pytest.approx(5e-6)
    assert dev["collective_s"] == pytest.approx(3e-6)
    assert dev["exposed_collective_s"] == pytest.approx(2e-6)
    assert [n for n, _ in red["device_ops"]] == ["fusion.2", "all-reduce.3"]
    name, secs = red["idle_gaps"][0]
    assert (name, secs) == ("PjitFunction(step)", pytest.approx(4e-6))
