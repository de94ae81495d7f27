"""Trace reduction on hand-made events."""
import pytest

import xplane

KERNELS = {"policy_mlp": r"^policy_mlp(\.\d+)?$"}


def test_reduce_events_hand_made():
    host = [("window", 100, 200), ("window", 200, 1100),
            ("rank", 150, 400), ("PjitFunction(policy_mlp)", 300, 350),
            ("before", 0, 90)]
    dev = [("policy_mlp.1", 320, 360), ("copy.2", 350, 380),
           ("fusion.7", 700, 800), ("outside", 1200, 1300),
           ("policy_mlp", 90, 110)]
    red = xplane.reduce_events(host, [dev], KERNELS)
    assert red["window_s"] == pytest.approx(1000e-9)
    # busy: [100,110] + [320,380] + [700,800] = 10 + 60 + 100
    assert red["busy_s"] == pytest.approx(170e-9)
    assert red["kernel_s"]["policy_mlp"] == pytest.approx(50e-9)
    assert red["kernel_calls"]["policy_mlp"] == 2
    ops = dict(red["device_ops"])
    assert ops == pytest.approx({"fusion": 100e-9, "policy_mlp": 50e-9,
                                 "copy": 30e-9})
    # gaps [110,320], [380,700], [800,1100] against the innermost open
    # span: window 40 + rank 150 + PjitFunction 20; rank 20 + window 300;
    # window 300
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"window": 640e-9, "rank": 170e-9,
                                  "PjitFunction(policy_mlp)": 20e-9})


def test_busy_is_averaged_over_devices_that_ran():
    host = [("window", 0, 100)]
    devs = [[("a", 0, 50)], [("b", 0, 30), ("c", 20, 40)], []]
    red = xplane.reduce_events(host, devs, KERNELS)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx(45e-9)


def test_no_window_no_result():
    assert xplane.reduce_events([("rank", 0, 1)], [[("a", 0, 1)]],
                                KERNELS) is None


def test_host_segments_nest():
    segs = xplane.host_segments([(10, 50, "rank"), (20, 30, "put"),
                                 (40, 70, "late")], 0, 100)
    assert segs == [(0, 10, "window"), (10, 20, "rank"), (20, 30, "put"),
                    (30, 40, "rank"), (40, 50, "late"), (50, 70, "late"),
                    (70, 100, "window")]


def test_hlo_names():
    assert xplane.hlo_name("%policy_mlp.1 = f32[4096]{0:T(1024)} "
                           "custom-call(f32[4096,8] %copy)") == "policy_mlp.1"
    assert xplane.hlo_name("%copy-start.4 = (f32[32]) copy-start(%b)") \
        == "copy-start.4"
    assert xplane.hlo_name("fusion.2") == "fusion.2"
    assert xplane.op_name("copy-start.4") == "copy-start"


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e: five ``policy_mlp`` calls on 4096
    rows, each inside a ``window`` and a ``rank`` span.  The device clock
    runs about 0.5 ms ahead of the host's in it, so the first call falls
    just before the first window span."""
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "small_policy_mlp.xplane.pb")
    red = xplane.reduce_xplane(path, KERNELS)
    assert red["devices"] == 1
    assert red["kernel_calls"]["policy_mlp"] == 4
    assert 0 < red["kernel_s"]["policy_mlp"] <= red["busy_s"] \
        < red["window_s"]
    assert red["device_ops"][0][0] == "policy_mlp"
    assert {n for n, _ in red["idle_gaps"]} <= {
        "window", "rank", "DevicePut", "PjitFunction(policy_mlp)",
        "ParseArguments", "PJRT_LoadedExecutable_Execute linkage",
        "PythonRefManager::CollectGarbage"}
