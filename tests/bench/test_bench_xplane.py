"""The trace reduction on small synthetic xplanes with known answers."""
import pytest

import _benchpath  # noqa: F401
from lib import xplane


def _space(device_ops, host_spans, devices=1):
    """Text-proto XSpace: device ops [(name, start_ns, dur_ns)] on each of
    `devices` TPU planes, host annotations [(name, start_ns, dur_ns)]."""
    names = sorted({n for n, _, _ in device_ops})
    ids = {n: i + 1 for i, n in enumerate(names)}
    planes = []
    for d in range(devices):
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
            f"duration_ps: {t * 1000} }} " for n, s, t in device_ops)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{n}" }} }} ' for n, i in ids.items())
        planes.append(f'planes {{ id: {d + 1} name: "/device:TPU:{d}" '
                      f'lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 '
                      f'{evs}}} {meta}}}')
    hn = sorted({n for n, _, _ in host_spans})
    hid = {n: i + 1 for i, n in enumerate(hn)}
    hevs = "".join(f"events {{ metadata_id: {hid[n]} offset_ps: {s * 1000} "
                   f"duration_ps: {t * 1000} }} " for n, s, t in host_spans)
    hmeta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }} ' for n, i in hid.items())
    planes.append(f'planes {{ id: 99 name: "/host:CPU" lines {{ id: 1 '
                  f'name: "python" timestamp_ns: 0 {hevs}}} {hmeta}}}')
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(" ".join(planes))


def test_busy_idle_and_kernel_time():
    # window [100, 1100) ns; ops overlap, one starts before the window
    ops = [("attend_protected", 50, 150), ("attend_protected", 300, 100),
           ("fusion.1", 350, 100), ("fusion.2", 900, 100)]
    host = [(xplane.WINDOW_SPAN, 100, 1000), ("engine.step", 100, 900),
            ("engine.scrub", 500, 350)]
    r = xplane.reduce_trace(_space(ops, host))
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100,200) + [300,450) + [900,1000) = 100 + 150 + 100
    assert r["busy_s"] == pytest.approx(350e-9)
    assert xplane.op_seconds(r, "attend_protected") == pytest.approx(200e-9)
    assert xplane.op_seconds(r, "fusion") == pytest.approx(200e-9)
    # gaps: [200,300) under engine.step, [450,900) mid 675 under
    # engine.scrub, [1000,1100) outside every span but the window
    gaps = [(name, round(g * 1e9)) for name, g in r["idle_gaps"]]
    assert gaps == [("engine.scrub", 450), ("engine.step", 100),
                    ("(no span)", 100)]
    assert r["device_ops"][0][0] == "attend_protected"


def test_busy_is_averaged_over_devices():
    ops = [("scan_syndromes", 0, 400)]
    host = [(xplane.WINDOW_SPAN, 0, 1000)]
    r = xplane.reduce_trace(_space(ops, host, devices=2))
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(400e-9)
    assert xplane.op_seconds(r, "scan_syndromes") == pytest.approx(400e-9)


def test_a_trace_without_window_or_device_is_refused():
    with pytest.raises(ValueError):
        xplane.reduce_trace(_space([("x", 0, 10)], [("engine.step", 0, 5)]))
    with pytest.raises(ValueError):
        xplane.reduce_trace(_space([], [(xplane.WINDOW_SPAN, 0, 5)],
                                   devices=0))


def test_union_of_intervals():
    iv = xplane.busy_intervals([("a", 0, 10), ("b", 5, 20), ("c", 30, 40)])
    assert iv == [(0, 20), (30, 40)]


def test_op_names_from_hlo_text():
    assert xplane.op_name("%fusion.3 = s32[4]{0} fusion(s32[4,4] %x)") == \
        "fusion.3"
    assert xplane.op_name("%attend_protected.1 = f32[16,1,32,64] "
                          "custom-call(...)") == "attend_protected.1"
    assert xplane.op_name("copy-start") == "copy-start"


def test_host_spans_come_from_the_window_thread():
    ops = [("scan_syndromes", 0, 100)]
    txt_host = [(xplane.WINDOW_SPAN, 0, 1000), ("bench.sweep", 0, 900)]
    pd = _space(ops, txt_host)
    _devs, host = xplane.split_planes(pd)
    assert {n for n, _, _ in host} == {xplane.WINDOW_SPAN, "bench.sweep"}


def test_idle_time_is_summed_by_span_and_runtime_names_skipped():
    ops = [("a", 0, 10), ("b", 20, 10), ("c", 40, 10), ("d", 90, 10)]
    host = [(xplane.WINDOW_SPAN, 0, 100), ("engine.decode", 0, 60),
            ("PjitFunction(floor_divide)", 10, 10), ("bench.step", 55, 40)]
    r = xplane.reduce_trace(_space(ops, host))
    # gaps [10,20) [30,40) in engine.decode (the runtime's annotation is
    # not a span), [50,60) midpoint 55 -> bench.step (innermost),
    # [60,90) -> bench.step
    assert [(n, round(t * 1e9)) for n, t in r["idle_gaps"]] == [
        ("bench.step", 40), ("engine.decode", 20)]
