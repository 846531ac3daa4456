"""Contract-auditor self-tests: every rule must catch its seeded violation
(with the right rule id and location), and the real repo must gate green.
"""

import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analyze.findings import Finding, Waivers, render_report
from repro.analyze.ir_rules import ChunkAudit, audit_chunk
from repro.analyze.lint import lint_file
from repro.compat import abstract_mesh, shard_map

U32 = (np.dtype(np.uint32),)


def _audit(traced, precision="int8", predicted=None, payload_dtypes=U32,
           payload_bytes=(), counters=None, working_set=None):
    return ChunkAudit(
        engine="test", precision=precision, variant="seeded",
        closed=traced.jaxpr, predicted=predicted or {},
        payload_dtypes=payload_dtypes, payload_bytes=payload_bytes,
        counters=counters or {}, working_set=working_set)


def _rules_fired(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------- IR layer


def test_ir_a_catches_float_arith_in_int8_body():
    tr = jax.jit(lambda x: (x.astype(jnp.float32) * 2.0).astype(jnp.int8)) \
        .trace(jax.ShapeDtypeStruct((8,), jnp.int8))
    found = audit_chunk(_audit(tr, precision="int8"))
    assert any(f.rule == "IR-A" and f.loc == "ir:test/int8/seeded"
               for f in found)
    # the same body is legal on the f32 path
    assert "IR-A" not in _rules_fired(audit_chunk(_audit(tr, "f32")))


def test_ir_b_catches_8bit_wire_in_bitplane_chunk():
    mesh = abstract_mesh((2,), ("data",))

    def body(x):
        return jax.lax.all_gather(x, "data", tiled=True)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P()))
    sds = jax.ShapeDtypeStruct((4, 8), jnp.int8,
                               sharding=NamedSharding(mesh, P("data")))
    found = audit_chunk(_audit(f.trace(sds), precision="bitplane",
                               predicted={"all_gather": 1}))
    msgs = [f.msg for f in found if f.rule == "IR-B"]
    assert any("on the wire" in m for m in msgs), found


def test_ir_b_catches_payload_byte_mismatch():
    mesh = abstract_mesh((2,), ("data",))

    def body(x):
        return jax.lax.all_gather(x, "data", tiled=True)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P()))
    sds = jax.ShapeDtypeStruct((4, 8), jnp.uint32,
                               sharding=NamedSharding(mesh, P("data")))
    found = audit_chunk(_audit(
        f.trace(sds), precision="bitplane", predicted={"all_gather": 1},
        payload_bytes=(4,)))   # wire is 2*8*4 = 64 B/device, declared 4
    assert any(f.rule == "IR-B" and "declared boundary payload" in f.msg
               for f in found)


def test_ir_c_catches_collective_count_mismatch():
    mesh = abstract_mesh((2,), ("data",))

    def body(x):
        return jax.lax.psum(x, "data")

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P()))
    sds = jax.ShapeDtypeStruct((4,), jnp.int32,
                               sharding=NamedSharding(mesh, P("data")))
    found = audit_chunk(_audit(f.trace(sds), precision="f32",
                               predicted={"psum": 3}))
    assert any(f.rule == "IR-C" and "psum" in f.msg for f in found)
    # correct prediction: silent
    ok = audit_chunk(_audit(f.trace(sds), precision="f32",
                            predicted={"psum": 1}))
    assert "IR-C" not in _rules_fired(ok)


def test_ir_c_scales_counts_by_scan_length():
    mesh = abstract_mesh((2,), ("data",))

    def body(x):
        def step(c, _):
            return jax.lax.psum(c, "data"), None
        out, _ = jax.lax.scan(step, x, None, length=5)
        return out

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P()))
    sds = jax.ShapeDtypeStruct((4,), jnp.float32,
                               sharding=NamedSharding(mesh, P("data")))
    ok = audit_chunk(_audit(f.trace(sds), precision="f32",
                            predicted={"psum": 5}))
    assert "IR-C" not in _rules_fired(ok)


def test_ir_d_catches_host_callback():
    def fn(x):
        return jax.pure_callback(
            lambda a: np.asarray(a), jax.ShapeDtypeStruct((4,), np.float32),
            x)

    tr = jax.jit(fn).trace(jax.ShapeDtypeStruct((4,), jnp.float32))
    found = audit_chunk(_audit(tr, precision="f32"))
    assert any(f.rule == "IR-D" and "callback" in f.msg for f in found)


def test_ir_e_catches_i32_counter_and_accepts_modular_publish():
    from repro.core.pbit import flips_publish

    bad = jax.jit(lambda fl, d: fl + d).trace(
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32))
    found = audit_chunk(_audit(bad, "f32", counters={"flips": 0}))
    assert any(f.rule == "IR-E" and "`add`" in f.msg for f in found)

    good = jax.jit(flips_publish).trace(
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.uint32))
    ok = audit_chunk(_audit(good, "f32", counters={"flips": 0}))
    assert "IR-E" not in _rules_fired(ok)


def test_ir_e_checks_seq_dtype():
    tr = jax.jit(lambda s: s + 1).trace(jax.ShapeDtypeStruct((), jnp.int32))
    found = audit_chunk(_audit(tr, "f32", counters={"seq": 0}))
    assert any(f.rule == "IR-E" and "seq" in f.msg for f in found)


def test_ir_f_catches_working_set_drift():
    mesh = abstract_mesh((2,), ("data",))

    def body(x):
        return x + jnp.float32(1)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=P("data"),
                          out_specs=P("data")))
    sds = jax.ShapeDtypeStruct((64,), jnp.float32,
                               sharding=NamedSharding(mesh, P("data")))
    tr = f.trace(sds)
    found = audit_chunk(_audit(tr, "f32",
                               working_set=(10_000_000, (4, 4, 4))))
    assert any(f.rule == "IR-F" for f in found)
    ok = audit_chunk(_audit(tr, "f32", working_set=(512, (4, 4, 4))))
    assert "IR-F" not in _rules_fired(ok)


# --------------------------------------------------------------- AST layer


def _lint(tmp_path, src):
    p = tmp_path / "seeded.py"
    p.write_text(textwrap.dedent(src))
    return lint_file(p, "seeded.py")


def test_al_random_catches_np_random_in_jitted_fn(tmp_path):
    found = _lint(tmp_path, """\
        import numpy as np
        import jax

        @jax.jit
        def f(x):
            return x + np.random.rand()
    """)
    assert any(f.rule == "AL-RANDOM" and f.loc == "seeded.py:6"
               for f in found)


def test_al_random_catches_time_in_scanned_fn(tmp_path):
    found = _lint(tmp_path, """\
        import time
        import jax

        def run(xs):
            def step(c, x):
                return c + time.time(), x
            return jax.lax.scan(step, 0.0, xs)
    """)
    assert any(f.rule == "AL-RANDOM" and "time.time" in f.msg
               for f in found)


def test_al_random_ignores_host_side_randomness(tmp_path):
    found = _lint(tmp_path, """\
        import numpy as np

        def seed_spawner():
            return np.random.randint(0, 2**31)
    """)
    assert not found


def test_al_key_catches_array_in_cache_key(tmp_path):
    found = _lint(tmp_path, """\
        import numpy as np
        _pool_cache = {}

        def put(labels, n):
            k = np.asarray(labels)
            _pool_cache[(k, n)] = 1
    """)
    assert any(f.rule == "AL-KEY" and f.loc == "seeded.py:6" for f in found)


def test_al_key_accepts_digested_keys(tmp_path):
    found = _lint(tmp_path, """\
        import hashlib
        import numpy as np
        _pool_cache = {}

        def put(labels, n):
            k = hashlib.sha1(np.asarray(labels).tobytes()).hexdigest()
            _pool_cache[(k, n)] = 1
    """)
    assert not found


def test_al_lock_catches_unlocked_counter(tmp_path):
    src = """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0   # guarded_by: _lock

            def bump(self):
                self.n += 1

            def read_ok(self):
                with self._lock:
                    return self.n

            def held_ok(self):  # lock_held: _lock
                return self.n
    """
    found = _lint(tmp_path, src)
    assert [f.loc for f in found if f.rule == "AL-LOCK"] == ["seeded.py:9"]


def test_al_lock_honors_condition_alias(tmp_path):
    found = _lint(tmp_path, """\
        import threading

        class S:
            def __init__(self):
                self._lock = threading.Lock()
                self._cv = threading.Condition(self._lock)  # lock_alias: _lock
                self.jobs = []   # guarded_by: _lock

            def wait_ok(self):
                with self._cv:
                    return len(self.jobs)
    """)
    assert not [f for f in found if f.rule == "AL-LOCK"]


def test_al_except_catches_silent_swallow_around_exchange(tmp_path):
    found = _lint(tmp_path, """\
        def pump(eng, m):
            try:
                ghosts = eng.exchange_block(m)
            except Exception:
                pass
            return m
    """)
    assert any(f.rule == "AL-EXCEPT" and f.loc == "seeded.py:4"
               for f in found)


def test_al_except_accepts_handled_exchange(tmp_path):
    found = _lint(tmp_path, """\
        def pump(eng, m, health):
            try:
                ghosts = eng.exchange_block(m)
            except Exception as e:
                health.record(e)
                raise
            return m
    """)
    assert not [f for f in found if f.rule == "AL-EXCEPT"]


# ---------------------------------------------------------------- deadcode


def test_al_dead_flags_unreachable_module(tmp_path):
    from repro.analyze import deadcode
    (tmp_path / "src" / "repro").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (tmp_path / "src" / "repro" / "__init__.py").write_text("")
    (tmp_path / "src" / "repro" / "used.py").write_text("X = 1\n")
    (tmp_path / "src" / "repro" / "dead.py").write_text("Y = 2\n")
    (tmp_path / "tests" / "test_used.py").write_text(
        "from repro.used import X\n")
    found = deadcode.run(tmp_path)
    assert [f.loc for f in found] == ["src/repro/dead.py"]


def test_al_dead_sees_imports_inside_runpy_strings(tmp_path):
    from repro.analyze import deadcode
    (tmp_path / "src" / "repro").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (tmp_path / "src" / "repro" / "__init__.py").write_text("")
    (tmp_path / "src" / "repro" / "sub.py").write_text("Z = 3\n")
    (tmp_path / "tests" / "test_sub.py").write_text(
        'SNIPPET = """\nfrom repro.sub import Z\n"""\n')
    assert deadcode.run(tmp_path) == []


# ----------------------------------------------------------------- waivers


def test_waivers_match_and_unused(tmp_path):
    wf = tmp_path / "waivers.txt"
    wf.write_text(
        "AL-DEAD  src/repro/x.py   # CLI entry point\n"
        "IR-C     ir:lattice/*     # never matched\n")
    w = Waivers.load(wf)
    hit = Finding("AL-DEAD", "src/repro/x.py", "dead")
    miss = Finding("AL-DEAD", "src/repro/y.py", "dead")
    assert w.match(hit) == "CLI entry point"
    assert w.match(miss) is None
    assert [e[0] for e in w.unused()] == ["IR-C"]


def test_waivers_strip_line_numbers(tmp_path):
    wf = tmp_path / "waivers.txt"
    wf.write_text("AL-LOCK  src/repro/serve/x.py  # reviewed\n")
    w = Waivers.load(wf)
    assert w.match(Finding("AL-LOCK", "src/repro/serve/x.py:123", "m"))


def test_waivers_reject_rationale_free_lines(tmp_path):
    wf = tmp_path / "waivers.txt"
    wf.write_text("AL-DEAD src/repro/x.py\n")
    with pytest.raises(ValueError):
        Waivers.load(wf)


def test_render_report_exit_code(tmp_path):
    w = Waivers([], path=None)
    text, code = render_report({"lint": []}, w)
    assert code == 0 and "CLEAN" in text
    text, code = render_report(
        {"lint": [Finding("AL-KEY", "a.py:1", "bad key")]}, w)
    assert code == 1 and "FAIL" in text and "AL-KEY" in text


# --------------------------------------------------- repo-level acceptance


@pytest.fixture(scope="module")
def repo_audits():
    from repro.analyze.configs import build_audits
    return build_audits()


def test_ir_enumeration_covers_every_engine_precision(repo_audits):
    from repro.engines.base import ENGINE_PRECISIONS
    audits, failures = repo_audits
    assert failures == [], failures
    covered = {(a.engine, a.precision) for a in audits}
    wanted = {(e, p) for e, ps in ENGINE_PRECISIONS.items() for p in ps}
    assert wanted <= covered
    # both mesh engines' degraded exchanges are audited too
    variants = {(a.engine, a.variant) for a in audits}
    for eng in ("dsim_dist", "lattice"):
        assert (eng, "degrade") in variants
        assert (eng, "degrade+codes") in variants


def test_repo_gates_green(repo_audits):
    """The committed tree must pass its own auditor (CI's analyze step)."""
    from repro.analyze.ir_rules import audit_chunk as audit
    from repro.analyze.runner import (DEFAULT_WAIVER_FILE, repo_root,
                                      run_deadcode, run_lint)
    audits, _ = repo_audits
    findings = [f for a in audits for f in audit(a)]
    root = repo_root()
    findings += run_lint(root) + run_deadcode(root)
    waivers = Waivers.load(root / DEFAULT_WAIVER_FILE)
    unwaived = [f for f in findings if waivers.match(f) is None]
    assert unwaived == [], "\n".join(f.render() for f in unwaived)
