"""The benchmark's yardstick: trace reduction and kernel byte counts."""

import gzip
import os

import pytest

import kernel_bytes
import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "small_trace.xplane.pb.gz")


def test_busy_union_of_overlapping_ops():
    # a while loop holding two kernels, a third op overlapping its end, and
    # one op alone after a gap: busy is the union, not the sum
    evs = [("while", 0, 100), ("k", 10, 30), ("k", 50, 40),
           ("copy", 90, 30), ("k", 200, 50)]
    assert xplane.union(evs) == [(0, 120), (200, 250)]
    assert xplane.busy_ns(evs) == 170
    assert xplane.gaps(evs, 0, 300) == [(120, 200), (250, 300)]
    assert xplane.gaps(evs, -10, 250) == [(-10, 0), (120, 200)]
    idle = 1 - xplane.busy_ns(evs) / 300
    assert idle == pytest.approx(130 / 300)


def test_per_kernel_sums_and_names():
    evs = [("k", 0, 10), ("k", 20, 5), ("e", 30, 1)]
    assert xplane.per_name(evs) == {"k": (2, 15.0), "e": (1, 1.0)}
    assert xplane.op_name(
        "%pbit_brick_update_int.8 = (s8[100,100,100]) custom-call(...)") \
        == "pbit_brick_update_int"
    assert xplane.op_name("%vmap_jit_brick_energy__.2 = f32[8,1,1] x") \
        == "vmap_jit_brick_energy__"
    assert xplane.op_name("%while = (s32[]) while(...)") == "while"
    assert xplane.op_name("%copy-start.11 = (s8[]) copy-start(x)") \
        == "copy-start"


def test_gap_named_by_benchmark_span_first():
    host = [("PjitFunction(run)", 0, 1000), ("bench.init_state", 100, 50),
            ("bench.advance", 160, 5)]
    assert xplane.name_gap((110, 140), host) == "bench.init_state"
    assert xplane.name_gap((500, 600), host) == "PjitFunction(run)"
    assert xplane.name_gap((2000, 2100), host) == "host idle"


def test_recorded_trace(tmp_path):
    """A trace of two chunks of an L=16 lattice: every device op lies in
    the union, the sweep kernel and the energy readout carry their names,
    and the benchmark's span is on the host side."""
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(open(FIXTURE, "rb").read()))
    devices, host = xplane.load(str(path))
    assert devices, "no device plane in the fixture"
    for evs in devices.values():
        names = xplane.per_name(evs)
        busy = xplane.busy_ns(evs)
        t0 = min(t for _, t, _ in evs)
        t1 = max(t + d for _, t, d in evs)
        assert 0 < busy <= t1 - t0
        assert busy <= sum(s for _, s in names.values())
        gaps = xplane.gaps(evs, t0, t1)
        assert busy + sum(b - a for a, b in gaps) == pytest.approx(t1 - t0)
        assert any("pbit_brick_sweep_int" == n for n in names)
        assert any("brick_energy" in n for n in names)
    assert any(n == "bench.advance" for n, _, _ in host)


def test_phase_kernel_bytes_hand_count():
    # one color phase of the whole 100^3 brick (x-tiles of 25 planes are
    # grid steps of the same launch): int8 mask, h, 6 couplings, spins;
    # uint32 LFSR states in and out; six 32-bit halo faces; 13 thresholds
    n = 100 ** 3
    reads = n + n + 6 * n + n + 4 * n + 4 * 2 * (3 * 100 * 100) + 4 * 13
    writes = n + 4 * n
    assert kernel_bytes.phase_int8((100, 100, 100)) == reads + writes \
        == 18_240_052


def test_fused_kernel_bytes_hand_count():
    # 4 sweeps of the 25x100x100 brick of one of four chips: two color
    # masks, h, 6 couplings, spins (int8), LFSR in/out, halos (two 100x100
    # x faces, four 25x100 y/z rows), 4 threshold rows, a flip count
    n = 25 * 100 * 100
    halos = 4 * 2 * (100 * 100 + 25 * 100 + 25 * 100)
    reads = 2 * n + n + 6 * n + n + 4 * n + halos + 4 * 13 * 4
    writes = n + 4 * n + 4
    assert kernel_bytes.sweep_int8((25, 100, 100), 4) == reads + writes \
        == 4_870_212


def test_layer_metrics_read_the_recorded_trace(tmp_path):
    """Every trace-sourced reader finds its kernels in a real trace (the
    fixture runs the fused kernel on a 16^3 brick, 4 sweeps a launch)."""
    import harness
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(
        gzip.decompress(open(FIXTURE, "rb").read()))
    ctx = harness.trace_context(str(tmp_path), 0.05, 1)
    ctx.update(peaks={"hbm_bytes_per_s": 819e9}, energy_kernel="brick_energy",
               sweep_kernels={"pbit_brick_sweep_int":
                              kernel_bytes.sweep_int8((16, 16, 16), 4)})
    read = {}
    for name in ("kernel.pbit_sweep_roofline", "driver.record_share",
                 "device.idle_share.updates"):
        read[name] = harness.load_module(os.path.join(
            harness.BENCH, "metrics", name + ".py"), "m").read(ctx)
        assert read[name] is not None and 0 < read[name] <= 100, read
    assert ctx["breakdown"]["device_ops"][0][0] == "pbit_brick_sweep_int"
