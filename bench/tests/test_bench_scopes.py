"""The program-span and scope reduction (``bench/scopes.py``) on a
hand-made trace with hand-computed answers."""
import pytest

from bench import harness, scopes, trace_reduce

CHUNK = "jit__sequential_fused_impl"


def ev(name, start_us, dur_us):
    return (name, start_us * 1e3, dur_us * 1e3)


def trace():
    """One device, one host thread: a solve of two chunks.

    host:   bench.solve_batched 0-230, repro.solve_batched 1-199,
            repro.draw_picks 1.5-11, two repro.chunk (11-101, 101-191)
            each with picks_slice, launch, resid_wait, on_record, then
            repro.result 191-199
    device: jit__randint(3) 2-10 runs fusion.3 (a name the chunk module
            has too); each chunk module run holds fusion (20 us),
            sweep_rows_gs.2 (30), fusion.3 (15), copy.7 (3)
    """
    host = [ev("bench.solve_batched", 0, 230),
            ev("repro.solve_batched", 1, 198),
            ev("repro.draw_picks", 1.5, 9.5), ev("repro.result", 191, 8)]
    for c0 in (11, 101):
        host += [ev("repro.chunk", c0, 90), ev("repro.picks_slice", c0, 2),
                 ev("repro.launch", c0 + 2, 8),
                 ev("repro.resid_wait", c0 + 10, 75),
                 ev("repro.on_record", c0 + 85, 5)]
    modules = [ev("jit__randint(3)", 2, 8)]
    ops = [ev("%fusion.3 = u32[8] fusion(u32[8] %p)", 2, 8)]
    for m0 in (20, 110):
        modules.append(ev(f"{CHUNK}(7)", m0, 70))
        ops += [ev("%fusion = f32[8,4] fusion(s32[8] %p)", m0, 20),
                ev("%sweep_rows_gs.2 = f32[8,4] custom-call()", m0 + 20, 30),
                ev("%fusion.3 = f32[4] fusion(f32[8,4] %x)", m0 + 50, 15),
                ev("%copy.7 = f32[8,4] copy(f32[8,4] %x)", m0 + 65, 3)]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": host}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]},
    ]


#: what the chunk executable's compiled HLO says of its instructions
HLO = {(CHUNK, "fusion"): "repro.row_windows",
       (CHUNK, "sweep_rows_gs.2"): "repro.sweep",
       (CHUNK, "fusion.3"): "repro.residual",
       (CHUNK, "copy.7"): None}


def test_existing_keys_unchanged():
    planes = trace()
    want = trace_reduce.reduce(planes)
    got = scopes.reduce(planes, hlo_scopes=HLO)
    assert {k: got[k] for k in want} == want


def test_scope_seconds_keyed_by_module():
    s = scopes.reduce(trace(), hlo_scopes=HLO)
    assert s["scope_s"] == pytest.approx({
        "repro.row_windows": 40e-6, "repro.sweep": 60e-6,
        "repro.residual": 30e-6,
        # the randint module's fusion.3 (8 us) and both copy.7 (6 us)
        scopes.NO_SCOPE: 14e-6})
    rows = {(m, i): (sc, v) for m, i, sc, v in s["module_ops"]}
    assert rows[("jit__randint", "fusion.3")] == (scopes.NO_SCOPE,
                                                   pytest.approx(8e-6))
    assert rows[(CHUNK, "fusion.3")] == ("repro.residual",
                                         pytest.approx(30e-6))
    # trace_reduce's op_s still merges the two fusion.3
    assert s["op_s"]["fusion.3"] == pytest.approx(38e-6)
    assert sum(s["scope_s"].values()) == pytest.approx(s["busy_s"])
    # the map names every op of the chunk module
    assert s["scope_coverage"] == 1.0


def test_idle_by_innermost_program_span():
    s = scopes.reduce(trace(), hlo_scopes=HLO)
    # busy 2-10, 20-88, 110-178 of 0-230; gaps: 0-2 (mid 1: solve_batched),
    # 10-20 (mid 15: launch), 88-110 (mid 99: on_record), 178-230 (mid
    # 204: only the benchmark's span is open)
    assert dict(s["idle_by_program_span"]) == pytest.approx({
        "repro.solve_batched": 2e-6, "repro.launch": 10e-6,
        "repro.on_record": 22e-6, scopes.NO_SPAN: 52e-6})
    assert sum(v for _, v in s["idle_by_program_span"]) == pytest.approx(
        sum(v for _, v in s["idle_by_span"]))


def test_program_span_counts():
    s = scopes.reduce(trace(), hlo_scopes=HLO)
    assert s["program_spans"] == {
        "repro.solve_batched": 1, "repro.draw_picks": 1, "repro.result": 1,
        "repro.chunk": 2, "repro.picks_slice": 2, "repro.launch": 2,
        "repro.resid_wait": 2, "repro.on_record": 2}


def test_hlo_scopes_fusion_takes_its_root():
    text = """HloModule jit_chunk, entry_computation_layout={()->f32[4]}

%fused_computation.2 (param_0: f32[8,4]) -> f32[4] {
  %param_0 = f32[8,4]{1,0} parameter(0)
  ROOT %reduce.1 = f32[4]{0} reduce(%param_0), metadata={op_name="jit(chunk)/repro.residual/reduce_sum"}
}

ENTRY %main.9 (p: f32[8,4]) -> f32[4] {
  %p = f32[8,4]{1,0} parameter(0), metadata={op_name="p"}
  %copy.3 = f32[8,4]{0,1} copy(%p)
  %sweep_rows_gs.2 = f32[8,4]{1,0} custom-call(%copy.3), metadata={op_name="jit(chunk)/repro.sweep/jit(sweep_rows_gs)/pallas_call"}
  ROOT %fusion.2 = f32[4]{0} fusion(%sweep_rows_gs.2), kind=kLoop, calls=%fused_computation.2
}
"""
    assert scopes.hlo_scopes(text) == {
        ("jit_chunk", "param_0"): None,
        ("jit_chunk", "reduce.1"): "repro.residual",
        ("jit_chunk", "p"): None,
        ("jit_chunk", "copy.3"): None,
        ("jit_chunk", "sweep_rows_gs.2"): "repro.sweep",
        ("jit_chunk", "fusion.2"): "repro.residual"}


def test_innermost_scope():
    assert scopes.innermost_scope(
        "jit(f)/repro.sweep/jit(g)/repro.sweep_operands/reshape") == \
        "repro.sweep_operands"
    assert scopes.innermost_scope("jit(f)/while/body/add") is None


def _readings(summary):
    return harness.Readings({"updates": 8}, summary, {}, None, 230e-6)


def test_per_chunk_readers():
    r = _readings(scopes.reduce(trace(), hlo_scopes=HLO))
    got = {k: read(r) for k, read in scopes.READERS.items()}
    assert got == pytest.approx({
        "operator.row_windows_ms_per_chunk": 40e-3 / 2,
        # a scope with no device op reads 0 where a trace was taken
        "kernel.operands_ms_per_chunk": 0.0,
        "engine.residual_ms_per_chunk": 30e-3 / 2,
        # idle inside the program's spans: 2 + 10 + 22 us over 2 chunks
        "engine.host_gap_ms_per_chunk": 34e-3 / 2})


def test_readers_none_without_a_reading():
    untraced = _readings(None)
    # a program without spans: the benchmark's trace only
    planes = trace()
    planes[0]["lines"][0]["events"] = [
        e for e in planes[0]["lines"][0]["events"]
        if e[0].startswith("bench.")]
    no_spans = _readings(scopes.reduce(planes, hlo_scopes=HLO))
    for read in scopes.READERS.values():
        assert read(untraced) is None
        assert read(no_spans) is None
    # spans but no scope map: no scope reading, the gap stays
    no_scopes = _readings(scopes.reduce(trace()))
    assert "scope_s" not in no_scopes.trace
    assert scopes.row_windows_ms_per_chunk(no_scopes) is None
    assert scopes.host_gap_ms_per_chunk(no_scopes) == pytest.approx(
        34e-3 / 2)


@pytest.mark.parametrize("wrong", ["module", "instruction"])
def test_readers_none_when_the_map_is_of_another_executable(wrong):
    """A map whose module or instruction names miss the traced chunk
    reads as missing, not as 0 ms."""
    if wrong == "module":
        bad = {("jit_other", i): sc for (_, i), sc in HLO.items()}
    else:
        bad = {(m, i + "_old"): sc for (m, i), sc in HLO.items()}
    s = scopes.reduce(trace(), hlo_scopes=bad)
    assert "scope_s" not in s and "module_ops" not in s
    assert s["scope_coverage"] == (None if wrong == "module" else 0.0)
    r = _readings(s)
    for name in ("operator.row_windows_ms_per_chunk",
                 "kernel.operands_ms_per_chunk",
                 "engine.residual_ms_per_chunk"):
        assert scopes.READERS[name](r) is None
    # the host gap needs no map
    assert scopes.host_gap_ms_per_chunk(r) == pytest.approx(34e-3 / 2)


def test_readers_none_below_min_coverage():
    # without copy.7 (3 of 68 us a chunk) the map names 95.6 % of the
    # chunk module's time; without sweep_rows_gs.2 too, 51.5 %
    part = {k: v for k, v in HLO.items() if k[1] != "copy.7"}
    s = scopes.reduce(trace(), hlo_scopes=part)
    assert s["scope_coverage"] == pytest.approx(65 / 68)
    assert "scope_s" in s
    del part[(CHUNK, "sweep_rows_gs.2")]
    s = scopes.reduce(trace(), hlo_scopes=part)
    assert s["scope_coverage"] == pytest.approx(35 / 68)
    assert scopes.row_windows_ms_per_chunk(_readings(s)) is None


def test_chunk_hlo_maps_the_program_scopes():
    """The map of the executables a solve launches, recorded as the
    program calls them and compiled here."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine, random_sparse_spd
    from repro.core.operators import CsrOp
    prob = random_sparse_spd(128, row_nnz=4, n_rhs=2, seed=1)
    op = CsrOp.from_dense(prob.A)
    jitted = engine._sequential_fused_impl
    with scopes.launched(engine) as calls:
        res = engine.solve_batched(
            op, jnp.asarray(prob.b), action="gs", key=jax.random.key(1),
            num_iters=256, tol=np.zeros(2, np.float32), record_every=128,
            fused=True)
    assert engine._sequential_fused_impl is jitted
    assert int(res.iters_run) == 256
    assert set(calls) == {"_sequential_fused_impl"}
    got = scopes.hlo_scopes(scopes.compiled_text(calls))
    assert {m for m, _ in got} == {CHUNK}
    assert {"repro.row_windows", "repro.sweep", "repro.residual"} <= set(
        got.values())


def test_script_refuses_without_a_tpu():
    """``python3 bench/scopes.py`` starts from the checkout's root, finds
    no TPU here, and exits 2 with no result line."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/scopes.py", "--workload", "spd16k.solve",
         "--seed", "5", "--seconds", "1"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=harness.ROOT)
    assert out.returncode == 2
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr
