"""The reduction of a trace by the program's own names: device time per
program from the "XLA Modules" line, idle time by innermost program span."""

import gzip
import json
import os

import pytest

import program_trace as pt
import xplane

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "small_trace.xplane.pb.gz")


def test_modules_reduce_to_programs(tmp_path):
    """The fixture's "XLA Modules" line: the chunk runner ("jit_run") ran
    twice, two distinct "jit_block" programs (halo refresh and energy
    readout) three times; each program's time lies inside the busy time."""
    assert pt.program_name("jit_block(11034964242294357929)") == "block"
    assert pt.program_name("jit_lattice_energy(7)") == "lattice_energy"
    assert pt.program_name("main") == "main"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.decompress(open(FIXTURE, "rb").read()))
    devices, modules, host = pt.load(str(path))
    assert (devices, host) == xplane.load(str(path))
    (mods,) = modules.values()
    programs = xplane.per_name(mods)
    assert programs["run"][0] == 2 and programs["block"][0] == 3
    assert not any(n.startswith("jit_") or "(" in n for n in programs)
    (ops,) = devices.values()
    assert programs["run"][1] <= xplane.busy_ns(ops)
    assert sum(s for _, s in programs.values()) >= xplane.busy_ns(ops) / 2


def test_program_spans_are_the_programs_own():
    host = [("bench.advance", 0, 10), ("cursor.chunk", 0, 5),
            ("np.asarray(jax.Array)", 0, 5), ("PjitFunction(run)", 0, 5),
            ("lattice.init_state.draw", 0, 5), ("ReadSyncFlag", 0, 5),
            ("pump.chunk", 3, 0)]
    assert [n for n, _, _ in pt.program_spans(host)] == \
        ["cursor.chunk", "lattice.init_state.draw"]


def test_idle_goes_to_the_innermost_span():
    spans = [("lattice.init_state", 0, 100),
             ("lattice.init_state.draw", 10, 30),
             ("lattice.halo_refresh", 60, 30),
             ("cursor.chunk", 200, 20), ("cursor.readout", 230, 20)]
    idle = [(20, 30),          # nested: under draw, inside init_state
            (35, 65),          # straddles draw's end and halo_refresh's start
            (150, 160),        # outside every span
            (215, 240)]        # straddles two sibling spans and the hole
    got = pt.idle_by_span(idle, spans)
    assert got == {"lattice.init_state.draw": 10 + 5,
                   "lattice.init_state": 20,
                   "lattice.halo_refresh": 5,
                   pt.OUTSIDE: 10 + 10,
                   "cursor.chunk": 5,
                   "cursor.readout": 10}
    assert sum(got.values()) == sum(b - a for a, b in idle)
    outer = [s for s in spans if s[0] == "lattice.init_state"]
    assert pt.idle_within(idle, outer) == 10 + 30
    assert pt.idle_within(idle, [s for s in spans
                                 if s[0].startswith("cursor.")]) == 15
    assert pt.idle_by_span(idle, []) == {pt.OUTSIDE: 75}


def test_reduction_sums_to_the_idle_time():
    devices = {"/device:TPU:0": [("k", 0, 10), ("k", 40, 10), ("k", 90, 10)]}
    modules = {"/device:TPU:0": [("lattice_chunk", 0, 10),
                                 ("lattice_energy", 40, 10)]}
    host = [("bench.advance", 0, 100), ("cursor.chunk", 5, 30),
            ("cursor.readout", 38, 4), ("lattice.init_state", 60, 20)]
    got = pt.reduce(devices, modules, host)
    assert got["idle_s"] == pytest.approx(70e-9)
    assert sum(got["idle_by_span"].values()) == pytest.approx(70e-9)
    assert got["idle_by_span"]["cursor.chunk"] == pytest.approx(25e-9)
    assert got["idle_in_span"]["cursor.*"] == pytest.approx(27e-9)
    assert got["idle_in_span"]["lattice.init_state"] == pytest.approx(20e-9)
    assert got["programs"]["lattice_energy"] == [1, 10e-9]
    # over a marked window the idle before the first op and after the
    # last counts too, under whatever span was open then
    host.append((pt.WINDOW_SPAN, -20, 130))
    host.append(("lattice.init_state", -20, 15))
    assert pt.traced_window(host) == (-20, 110)
    got = pt.reduce(devices, modules, host)
    assert got["idle_s"] == pytest.approx(100e-9)
    assert got["idle_between_ops_s"] == pytest.approx(70e-9)
    assert sum(got["idle_by_span"].values()) == pytest.approx(100e-9)
    assert got["idle_in_span"]["lattice.init_state"] == pytest.approx(35e-9)
    assert got["idle_by_span"][pt.OUTSIDE] == pytest.approx(38e-9)
    assert pt.traced_window(host[:4]) is None


def test_command_line_reads_a_profile_directory(tmp_path, capsys):
    (tmp_path / "plugins").mkdir()
    path = tmp_path / "plugins" / "t.xplane.pb"
    path.write_bytes(gzip.decompress(open(FIXTURE, "rb").read()))
    assert pt.main([str(tmp_path)]) == 0
    got = json.loads(capsys.readouterr().out)
    assert {"run", "block"} <= set(got["programs"])
    assert sum(got["idle_by_span"].values()) == \
        pytest.approx(got["idle_s"])
    assert pt.main([]) == 2
