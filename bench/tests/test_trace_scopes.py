"""bench/trace_scopes.py: the op names read from a trace recorded on four
v5e chips, and the scope fold and idle split of a small trace built here."""
import os

import pytest

from bench import trace_reduce as tr
from bench import trace_scopes as ts

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "psum_4chip.xplane.pb")


def test_op_names_come_from_each_tpu_planes_event_metadata():
    with open(TRACE, "rb") as f:
        names = ts.op_names(f.read())
    assert sorted(names) == [f"/device:TPU:{i}" for i in range(4)]
    for plane in names.values():
        by_instr = {tr.parse_op(text)[0]: op for text, op in plane.items()}
        assert by_instr["all-gather.5"] == "jit(body)/shard_map/all_gather"


@pytest.mark.parametrize("tf_op, scopes, backward", [
    ("jit(step)/jvp(evoformer)/while/body/msa_row_attn/dot_general",
     {"evoformer", "msa_row_attn"}, False),
    ("jit(step)/transpose(jvp(evoformer))/while/body/closed_call/"
     "transpose(jvp(opm))/mul", {"evoformer", "opm"}, True),
    ("jit(step)/transpose(jvp(loss))/vmap(jit(clip))/max", {"loss"}, True),
    ("jit(step)/shard_map/clip/sqrt", {"clip"}, False),
    ("jit(body)/shard_map/all_gather", set(), False),
])
def test_scopes_of_a_path(tf_op, scopes, backward):
    assert ts.scopes_of(tf_op) == scopes
    assert ts.is_backward(tf_op) is backward


# -- a small XSpace, written in the protobuf wire format ----------------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A message of ``(field number, int | str | bytes)`` fields."""
    out = b""
    for no, v in fields:
        if isinstance(v, int):
            out += _varint(no << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(no << 3 | 2) + _varint(len(v)) + v
    return out


STEP_NUM, TF_OP = 1, 2
US = 1000 * 1000                                    # picoseconds


def _plane(name, line, events, metadata) -> bytes:
    """An XPlane with one line of ``events`` ``(metadata id, start us, end
    us, int stats)`` and ``metadata`` ``{id: (name, tf_op or None)}``."""
    evs = [(4, _msg((1, mid), (2, s * US), (3, (e - s) * US),
                    *[(4, _msg((1, k), (4, v))) for k, v in stats.items()]))
           for mid, s, e, stats in events]
    meta = [(4, _msg((1, mid), (2, _msg(
        (1, mid), (2, text),
        *([(5, _msg((1, TF_OP), (5, op)))] if op is not None else [])))))
        for mid, (text, op) in metadata.items()]
    stats = [(5, _msg((1, sid), (2, _msg((1, sid), (2, sname)))))
             for sid, sname in ((STEP_NUM, "step_num"), (TF_OP, "tf_op"))]
    return _msg((1, 1), (2, name),
                (3, _msg((1, 1), (2, line), (3, 0), *evs)), *meta, *stats)


def _op(name, opcode):
    return f"%{name} = f32[4]{{0}} {opcode}(f32[4]{{0}} %p)"


# the device's ops, in microseconds of a 0-1000 window: steps 0 and 1 run
# over 100-500 and 600-1000 on the host
OPS = {  # metadata id: (instruction, tf_op, start, end)
    10: (_op("fusion.1", "fusion"),
         "jit(step)/jvp(evoformer)/while/body/msa_row_attn/dot_general:",
         150, 350),
    11: (_op("fusion.2", "fusion"), "jit(step)/transpose(jvp(evoformer))/"
         "while/body/closed_call/transpose(jvp(opm))/mul:", 350, 450),
    12: (_op("while.3", "while"), "jit(step)/jvp(evoformer)/while:",
         140, 460),
    13: (_op("copy.4", "copy"), None, 520, 560),
    14: (_op("fusion.5", "fusion"), "jit(step)/loss/vmap(jit(clip))/max:",
         650, 900),
}


@pytest.fixture(scope="module")
def built():
    from jax.profiler import ProfileData
    host = _plane("/host:CPU", "main",
                  [(1, 0, 1000, {}), (2, 100, 500, {STEP_NUM: 0}),
                   (2, 600, 1000, {STEP_NUM: 1}), (3, 520, 590, {})],
                  {1: (tr.WINDOW_EVENT, None), 2: ("step", None),
                   3: ("input_wait", None)})
    tpu = _plane("/device:TPU:0", "XLA Ops",
                 [(mid, s, e, {}) for mid, (_, _, s, e) in OPS.items()],
                 {mid: (text, op) for mid, (text, op, _, _) in OPS.items()})
    data = _msg((1, host), (1, tpu))
    space = ProfileData.from_serialized_xspace(data)
    return ts.reduce_xspace(space, ts.op_names(data)), tr.reduce_xspace(space)


def test_scope_fold_of_a_built_trace(built):
    red, _ = built
    ms = {k: {w: round(v * 1e6, 6) for w, v in row.items()}
          for k, row in red["planes"]["/device:TPU:0"]["scopes"].items()}
    assert ms == {
        "evoformer": {"fwd_s": 200.0, "bwd_s": 100.0},
        "msa_row_attn": {"fwd_s": 200.0, "bwd_s": 0.0},
        "opm": {"fwd_s": 0.0, "bwd_s": 100.0},
        "loss": {"fwd_s": 250.0, "bwd_s": 0.0},
        ts.UNSCOPED: {"fwd_s": 40.0, "bwd_s": 0.0},
    }
    per = ts.per_protein_ms(red, proteins=2)
    assert per["loss"] == pytest.approx({"fwd_ms": 0.125, "bwd_ms": 0.0})
    assert list(per)[0] == "evoformer"


def test_idle_split_by_step_spans(built):
    red, whole = built
    plane = red["planes"]["/device:TPU:0"]
    assert red["steps"] == [0, 1]
    assert red["window_s"] == pytest.approx(1000e-6)
    # busy 150-450, 520-560, 650-900: 590 us, as trace_reduce counts it
    assert plane["busy_s"] == pytest.approx(590e-6)
    assert whole["devices"]["/device:TPU:0"]["busy_s"] == pytest.approx(
        plane["busy_s"])
    # idle inside steps: 100-150 and 450-500 in step 0, 600-650 and
    # 900-1000 in step 1; between: 0-100, 500-520 and 560-600
    assert plane["idle_in_step_s"] == pytest.approx(250e-6)
    assert plane["idle_between_steps_s"] == pytest.approx(160e-6)
    idle_pct = 100 * (1 - whole["devices"]["/device:TPU:0"]["busy_s"]
                      / whole["window_s"])
    assert 100 * (plane["idle_in_step_s"] + plane["idle_between_steps_s"]) \
        / red["window_s"] == pytest.approx(idle_pct)
    step, gap, _ = red["idle_gaps_in_steps"][0]
    assert (step, gap) == (1, pytest.approx(100e-6))
    assert sorted(g[0] for g in red["idle_gaps_in_steps"]) == [0, 0, 1, 1]


def test_a_program_without_scopes_reads_all_unscoped():
    """The recorded trace's program has no named scopes: every op is
    ``(unscoped)``, and the idle split still adds up to the idle time."""
    red, whole = ts.reduce_file(TRACE), tr.reduce_file(TRACE)
    for name, plane in red["planes"].items():
        assert set(plane["scopes"]) == {ts.UNSCOPED}
        busy = whole["devices"][name]["busy_s"]
        assert plane["busy_s"] == pytest.approx(busy)
        assert plane["idle_in_step_s"] + plane["idle_between_steps_s"] \
            == pytest.approx(whole["window_s"] - busy)
