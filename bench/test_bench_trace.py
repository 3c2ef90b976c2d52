"""Trace reduction: busy union, idle share, per-path time, breakdown."""
import json
import re
from pathlib import Path

import pytest

from bench import trace as tr

FABRIC = f"{tr.FABRIC}|{tr.UNNAMED_SCATTER}"
RECORDED = Path(__file__).parent / "testdata" / "trace_mixtral_prefill.json"


def hand_record():
    return {
        "devices": {"/device:TPU:0": [
            ["while.0", 100, 300, "jit(s)/while", "while", "jit_s"],
            ["fusion.1", 100, 50, "jit(s)/jit(_dispatch_impl)/gather",
             "fusion", "jit_s"],
            ["dot.2", 140, 60, "jit(s)/dot_general", "fusion", "jit_s"],
            ["fusion.3", 300, 100, "<no op_name>/scatter", "fusion", "jit_s"],
            ["copy.4", 600, 10, "jit(s)/copy", "copy", "jit_s"],  # after
        ]},
        "host": [["bench.dispatch", 50, 20], ["bench.wait", 80, 400]],
    }


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 7)]


def test_hand_made_events():
    rec = hand_record()
    lo, hi = tr.window(rec)
    assert (lo, hi) == (50, 480)
    ops = rec["devices"]["/device:TPU:0"]
    assert tr.busy_ns(ops, lo, hi) == 300           # [100,400)
    assert tr.path_ns(ops, FABRIC, lo, hi) == 50 + 100
    s = tr.summarize(rec, {"fabric": FABRIC})
    assert s["window_s"] == pytest.approx(430e-9)
    assert s["busy_s"] == pytest.approx(300e-9)
    assert s["path_s"]["fabric"] == pytest.approx(150e-9)
    gaps = s["breakdown"]["idle_gaps"]
    # [50,100) falls between the two host spans, [400,480) inside bench.wait
    assert gaps == [["bench.wait", pytest.approx(80e-9)],
                    ["outside", pytest.approx(50e-9)]]
    top = s["breakdown"]["device_ops"]
    # the while loop's time is its body's: it is left out of the ops
    assert [k.split()[0] for k, _ in top] == ["jit_s:fusion.3", "jit_s:dot.2",
                                             "jit_s:fusion.1"]


def test_host_label_picks_innermost_span():
    spans = [["bench.outer", 0, 100], ["bench.inner", 10, 5]]
    assert tr.host_label(spans, 12) == "bench.inner"
    assert tr.host_label(spans, 50) == "bench.outer"
    assert tr.host_label(spans, 200) == "outside"


def test_window_needs_benchmark_spans():
    with pytest.raises(ValueError):
        tr.window({"devices": {}, "host": []})


HLO = """\
%fused_inner (p: bf16[4,8]) -> bf16[4,8] {
  %p = bf16[4,8]{1,0} parameter(0)
  ROOT %scatter.6 = bf16[4,8]{1,0:T(8,128)(2,1)} scatter(%p, %p, %p)
}

%fused_outer (q: bf16[4,8]) -> bf16[4,8] {
  %q = bf16[4,8]{1,0} parameter(0)
  ROOT %fusion.160 = bf16[4,8]{1,0} fusion(%q), kind=kCustom, calls=%fused_inner
}

%fused_dot (a: bf16[4,8]) -> bf16[4,8] {
  %a = bf16[4,8]{1,0} parameter(0)
  ROOT %dot.1 = bf16[4,8]{1,0} dot(%a, %a), metadata={op_type="dot" op_name="jit(step)/dot_general" source_line=3}
}

ENTRY %main (x: bf16[4,8]) -> (bf16[4,8], s32[]) {
  %x = bf16[4,8]{1,0} parameter(0)
  %fusion.12 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_dot
  %fusion.179 = bf16[4,8]{1,0} fusion(%x), kind=kCustom, calls=%fused_outer
  %gather.2 = bf16[4,8]{1,0} gather(%x, %x), metadata={op_name="jit(step)/jit(_dispatch_impl)/gather"}
  ROOT %tuple.3 = (bf16[4,8]{1,0}, s32[]) tuple(%fusion.12, %x)
}
"""


def test_hlo_op_paths_follow_fusions_to_their_root():
    paths = tr.hlo_op_paths(HLO)
    assert paths["fusion.12"] == "jit(step)/dot_general"
    assert paths["gather.2"] == "jit(step)/jit(_dispatch_impl)/gather"
    # the compiler left the scatter without an op name
    assert paths["fusion.179"] == "<no op_name>/scatter"
    assert paths["tuple.3"] == "<no op_name>/tuple"


def test_opcode_and_op_name_of_event_text():
    text = ("%while.51 = (s32[]{:T(128)}, bf16[4,2048]{1,0:T(8,128)(2,1)}) "
            "while((s32[], bf16[4,2048]) %tuple.134), condition=%c, body=%b")
    assert tr.opcode(text) == "while" and tr.op_name(text) == "while.51"
    text = "%fusion.179 = bf16[20488,4096]{1,0:T(8,128)(2,1)} fusion(%a)"
    assert tr.opcode(text) == "fusion" and tr.op_name(text) == "fusion.179"
    assert tr.opcode("fusion.3") is None


def _sweep_busy(ops, lo, hi):
    """Busy time by an independent sweep over interval edges."""
    edges = sorted({lo, hi} | {max(lo, min(hi, t)) for o in ops
                               for t in (o[1], o[1] + o[2])})
    busy = 0
    for a, b in zip(edges, edges[1:]):
        if any(o[1] <= a and b <= o[1] + o[2] for o in ops):
            busy += b - a
    return busy


def test_recorded_chip_trace():
    rec = json.loads(RECORDED.read_text())
    lo, hi = tr.window(rec)
    ops = next(iter(rec["devices"].values()))
    assert tr.busy_ns(ops, lo, hi) == _sweep_busy(ops, lo, hi)
    s = tr.summarize(rec, {"fabric": FABRIC})
    assert 0 < s["busy_s"] <= s["window_s"]
    # the recorded ops do not overlap: the fabric's time is a plain sum
    fabric = sum(o[2] for o in ops if re.search(FABRIC, o[3]))
    assert s["path_s"]["fabric"] == pytest.approx(fabric / 1e9)
    names = {o[0] for o in ops if re.search(FABRIC, o[3])}
    assert {"fusion.179", "fusion.182", "slice.94"} <= names
    assert "fusion.180" not in names          # the expert matmul
    top = s["breakdown"]["device_ops"]
    assert len(top) == 10 and top[0][0].startswith("jit_serve_step:fusion.180")
    times = [t for _, t in top]
    assert times == sorted(times, reverse=True)


def test_ops_of_several_programs_find_their_own_paths():
    """Two programs of one name (one function at two shapes) and a third:
    an op is looked up in its own module, and its output shape picks the
    program where instruction names collide."""
    small = HLO.replace("%main", "%main.1")
    big = small.replace("bf16[4,8]", "bf16[8,8]").replace(
        'op_name="jit(step)/dot_general"', 'op_name="jit(step)/big/dot"')
    other = "HloModule jit_plan\n\n" + HLO.replace(
        "jit(step)/jit(_dispatch_impl)", "jit(_plan_impl)")
    index = tr.hlo_index(["HloModule jit_step\n\n" + small,
                          "HloModule jit_step\n\n" + big, other])
    ev = "%fusion.12 = bf16[{}]{{1,0}} fusion(bf16[{}]{{1,0}} %x)"
    assert tr.lookup(index, "jit_step", ev.format("4,8", "4,8")) \
        == "jit(step)/dot_general"
    assert tr.lookup(index, "jit_step", ev.format("8,8", "8,8")) \
        == "jit(step)/big/dot"
    assert tr.lookup(index, "jit_plan", "%gather.2 = bf16[4,8]{1,0} "
                     "gather(bf16[4,8] %x)") == "jit(_plan_impl)/gather"
    assert tr.lookup(index, "jit_other", ev.format("4,8", "4,8")) == ""
    assert tr.module_of("jit_step(1234567)") == "jit_step"


def test_module_of_an_op_is_the_program_running_then():
    mods = [(0, 10, "jit_a"), (20, 30, "jit_b")]
    assert [tr._containing(mods, t) for t in (0, 9, 10, 15, 25, 31)] == \
        ["jit_a", "jit_a", "", "", "jit_b", ""]


def test_recorded_decode_trace_of_several_programs():
    """One B = 1 decode call of the served Mixtral cell and the host's
    greedy pick after it: each op knows its program, and the fabric's ops
    inside the decode program are found by their op-name path."""
    rec = json.loads((RECORDED.parent / "trace_mixtral_decode.json")
                     .read_text())
    lo, hi = tr.window(rec)
    ops = next(iter(rec["devices"].values()))
    assert {o[5] for o in ops} >= {"jit_decode_one", "jit__argmax"}
    assert tr.busy_ns(ops, lo, hi) == _sweep_busy(ops, lo, hi)
    s = tr.summarize(rec, {"fabric": FABRIC})
    fabric = [o for o in ops if re.search(FABRIC, o[3])]
    assert fabric and {o[5] for o in fabric} == {"jit_decode_one"}
    assert s["path_s"]["fabric"] == pytest.approx(
        sum(o[2] for o in fabric) / 1e9)
    assert 0 < s["path_s"]["fabric"] < 0.05 * s["busy_s"]
    top = s["breakdown"]["device_ops"]
    assert top[0][0].startswith("jit_decode_one:")
