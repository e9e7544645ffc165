"""The trace reduction, on a hand-made trace and on one recorded on a chip."""
from __future__ import annotations

import json
import os

import pytest

from bench import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# All times in ns.  Window [0, 450].
HAND = {
    "ops": [
        [0, "fusion.1", 100, 50, False],                # 100-150
        [0, "bvss_pull.2", 150, 30, True],              # 150-180
        [0, "while.3", 200, 100, False],                # 200-300, a parent
        [0, "fusion.4", 210, 40, False],                # 210-250
        [0, "finalize_pack_sweep.5", 250, 40, True],    # 250-290
        [0, "copy.6", 400, 100, False],                 # 400-500, cut at 450
    ],
    "modules": [[0, "jit_bfs(1)", 100, 200],
                [0, "jit_level_step(2)", 400, 100],
                [0, "jit_bfs(1)", 460, 10]],            # after the window
    "spans": [["window", 0, 450],
              ["levels_call", 50, 300],                 # 50-350
              ["await_arrival", 300, 150]],             # 300-450
}


def test_hand_made_trace():
    s = xplane.reduce(HAND)
    assert s.window_s == pytest.approx(450e-9)
    # busy: [100,180] + [200,300] + [400,450] = 80 + 100 + 50
    assert s.busy_s == pytest.approx(230e-9)
    assert s.idle_share == pytest.approx(1 - 230 / 450)
    # leaves only: the loop counts through its two children
    assert s.pallas_s == pytest.approx((30 + 40) * 1e-9)
    assert s.other_s == pytest.approx((50 + 40 + 50) * 1e-9)
    assert s.modules == {"jit_bfs(1)": 1, "jit_level_step(2)": 1}
    assert s.n_ops == 6
    # gaps [0,100] and [180,200] fall in levels_call; [300,400] has its
    # middle (350) in both spans and goes to the shorter, await_arrival
    assert s.idle_by_span == {"levels_call": pytest.approx(120e-9),
                              "await_arrival": pytest.approx(100e-9)}
    assert [g[0] for g in s.gaps] == ["levels_call", "await_arrival",
                                      "levels_call"]
    b = s.breakdown(top=2)
    assert [k for k, _ in b["device_ops"]] == ["fusion.1", "copy.6"]
    assert len(b["idle_gaps"]) == 2


def test_window_must_be_one_span():
    with pytest.raises(ValueError):
        xplane.reduce(dict(HAND, spans=[]))


def test_op_names_and_kernels():
    name = ('%bvss_spmm.5 = s32[128,128,8]{2,1,0} custom-call(u32[128,32] '
            '%fusion.6), custom_call_target="tpu_custom_call"')
    assert xplane._short(name) == "bvss_spmm.5 s32[128,128,8]"
    assert xplane._short("%copy-start.5 = (s32[8]{0}, s32[8]{0}) copy-start(")\
        == "copy-start.5"
    assert xplane._is_pallas(name)
    assert not xplane._is_pallas("%fusion.10 = s32[4097] fusion(...)")


def test_recorded_chip_trace():
    """Two single-source BFS calls (Kronecker scale 12) as one v5e chip
    recorded them: 459 device ops, the window cut around the calls.  The
    expected numbers were counted nanosecond by nanosecond over the
    window, independently of :func:`xplane.reduce`."""
    with open(os.path.join(DATA, "kron_scale12_two_calls.json")) as f:
        s = xplane.reduce(json.load(f))
    assert s.window_s == pytest.approx(7521729e-9)
    assert s.busy_s == pytest.approx(4594688e-9)
    # the loop and its conditionals (while.4, cond.5.clone.3) enclose the
    # level's ops; only the leaves are counted
    assert s.pallas_s == pytest.approx(7628e-9)
    assert s.other_s == pytest.approx(4578802e-9)
    assert s.modules == {"jit_bfs(9106897756157351124)": 2}
    assert s.n_ops == 459
    # 30 idle gaps, all inside the benchmark's levels_call spans; the two
    # longest are the host's work between one BFS and the next
    assert s.idle_by_span == {"levels_call": pytest.approx(2927041e-9)}
    assert len(s.gaps) == 30
    assert [g[1] for g in s.gaps[:2]] == pytest.approx(
        [(2911111 - 1420140) * 1e-9, (6638715 - 5203388) * 1e-9])


def test_extract_from_recorded_xplane():
    """One BFS call (Kronecker scale 8) traced on one v5e chip, read from
    the profiler's own file; the numbers were counted nanosecond by
    nanosecond over the window, independently of :func:`xplane.reduce`."""
    ev = xplane.extract(os.path.join(DATA, "kron_scale8_one_call.xplane.pb"),
                        {"window", "levels_call"})
    assert len(ev["ops"]) == 170
    kernels = sorted({o[1].split(".")[0] for o in ev["ops"] if o[4]})
    assert kernels == ["bvss_pull", "finalize_pack_sweep"]
    assert [m[1] for m in ev["modules"]] == ["jit_bfs(17490724723423993918)"]
    assert sorted(s[0] for s in ev["spans"]) == ["levels_call", "window"]
    s = xplane.reduce(ev)
    assert s.window_s == pytest.approx(2447171e-9)
    assert s.busy_s == pytest.approx(701895e-9)
    assert s.pallas_s == pytest.approx(1001e-9)
    assert s.other_s == pytest.approx(699110e-9)
    assert s.idle_by_span == {"levels_call": pytest.approx(1745276e-9)}
