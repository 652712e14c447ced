"""Tracecheck analyzer: planted defects must be *named*, clean entries
must stay clean, and every Pallas wrapper must guard its launch."""

import ast
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import defects
from repro.analysis.entrypoints import (Built, EntryPoint, SUITES,
                                        manifest, register_entrypoint)
from repro.analysis.ir_lint import IRLintError
from repro.analysis.lint import lint_source
from repro.analysis.tracecheck import (KINDS, assert_clean,
                                       jaxpr_dot_flops, run_tracecheck,
                                       trace_entry)
from repro.analysis.verify import VerifyError

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"


# ---------------------------------------------------------------------------
# planted defects: the analyzer names each corruption kind
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(defects.ALL))
def test_defect_named(kind):
    ep = defects.ALL[kind]
    report = trace_entry(ep, "8core", hlo=(kind == "cost-model"))
    assert not report.ok
    assert kind in {v.kind for v in report.violations}, \
        f"{ep.name}: expected a {kind!r} finding, got {report.violations}"


def test_defect_kinds_closed():
    assert set(defects.ALL) == set(KINDS)


def test_retrace_counts_cache_growth():
    report = trace_entry(defects.ALL["retrace"], "8core", hlo=False)
    assert report.retraces == 2          # one per swept static value


def test_f64_defect_under_x64():
    from defects.dtype import ENTRY_F64
    with jax.enable_x64(True):
        report = trace_entry(ENTRY_F64, "8core", hlo=False)
    assert "dtype" in {v.kind for v in report.violations}
    assert any("float64" in v.message for v in report.violations)


def test_clean_entry_stays_clean():
    ep = EntryPoint(
        "test.clean",
        lambda suite: Built(fn=lambda x, y: (x @ y).sum(),
                            args=(jnp.ones((8, 16)), jnp.ones((16, 4))),
                            sweep=((jnp.zeros((8, 16)),
                                    jnp.ones((16, 4)) * 3),)))
    report = trace_entry(ep, "8core", hlo=False)
    assert report.ok and report.retraces == 0
    assert report.flops_jaxpr == 2.0 * 8 * 16 * 4


def test_assert_clean_raises_verifyerror():
    with pytest.raises(VerifyError) as ei:
        assert_clean([trace_entry(defects.ALL["baked-const"], "8core",
                                  hlo=False)])
    assert "baked-const" in ei.value.kinds


# ---------------------------------------------------------------------------
# pass mechanics
# ---------------------------------------------------------------------------

def test_dot_flops_scan_multiplicity():
    def body(c, _):
        return c @ jnp.ones((16, 16)), None

    def fn(x):
        y, _ = jax.lax.scan(body, x, None, length=5)
        return y

    closed = jax.make_jaxpr(fn)(jnp.ones((4, 16)))
    assert jaxpr_dot_flops(closed) == 5 * 2.0 * 4 * 16 * 16


def test_host_sync_found_through_pjit():
    # the callback hides behind a nested jit — the AST rule can't see
    # it, the jaxpr walk must
    inner = jax.jit(defects.hostsync._leaky_norm)
    ep = EntryPoint("test.nested-sync",
                    lambda s: Built(fn=lambda x: inner(x) * 2.0,
                                    args=(jnp.ones(8),)))
    report = trace_entry(ep, "8core", hlo=False)
    assert "host-sync" in {v.kind for v in report.violations}


# ---------------------------------------------------------------------------
# manifest contract
# ---------------------------------------------------------------------------

def test_manifest_names_unique_and_suites_known():
    eps = manifest()
    names = [ep.name for ep in eps]
    assert len(names) == len(set(names))
    assert len(eps) >= 8
    for ep in eps:
        assert ep.suites, ep.name
        assert all(s in SUITES for s in ep.suites), ep.name


def test_register_entrypoint_rejects_duplicates():
    with pytest.raises(ValueError):
        register_entrypoint(manifest()[0])


def test_manifest_sched_entries_clean():
    # the cheap scheduling entries run end to end in-process; the model
    # entries (abstract compiles) are covered by the CLI gate in CI
    reports = run_tracecheck(
        quick=True, hlo=False,
        entries=["sched_score", "admission", "relax_pop"])
    assert len(reports) == 3
    assert_clean(reports)


# ---------------------------------------------------------------------------
# satellite: every Pallas wrapper guards its launch
# ---------------------------------------------------------------------------

#: the full public op list of kernels/ops.py — a new wrapper must be
#: added here AND call check_shape/check_gather_bounds before launch
OPS = {"flash_attention", "rmsnorm", "ssd_scan", "sched_score",
       "sim_relax_pop", "sim_relax_pop_sweeps", "flash_decode"}


def test_every_op_wrapper_checked():
    tree = ast.parse((SRC / "kernels" / "ops.py").read_text())
    defs = {n.name: n for n in tree.body if isinstance(n, ast.FunctionDef)
            if not n.name.startswith("_")}
    assert set(defs) == OPS, "ops.py public surface changed — update " \
                             "the pinned list and guard the new wrapper"
    for name, fn in defs.items():
        calls = {c.func.id for c in ast.walk(fn)
                 if isinstance(c, ast.Call)
                 and isinstance(c.func, ast.Name)}
        assert calls & {"check_shape", "check_gather_bounds"}, \
            f"ops.{name} launches without an ir_lint guard"


def test_flash_decode_bounds_guard():
    from repro.kernels import ops
    q = jnp.ones((2, 4, 16), jnp.float32)
    kc = jnp.ones((2, 32, 2, 16), jnp.float32)
    vc = jnp.ones((2, 32, 2, 16), jnp.float32)
    with pytest.raises(IRLintError):
        ops.flash_decode(q, kc, vc, jnp.array([8, 40]))   # 40 > T=32
    with pytest.raises(IRLintError):
        ops.flash_attention(jnp.ones((1, 8, 4, 16)),
                            jnp.ones((1, 9, 2, 16)),      # kv seq mismatch
                            jnp.ones((1, 8, 2, 16)))


# ---------------------------------------------------------------------------
# satellite: the dtype-promotion AST rule
# ---------------------------------------------------------------------------

def _rules(src):
    return [(v.rule, v.line) for v in lint_source(src, "x.py")]


def test_lint_flags_f64_ctor_in_device_scope():
    src = ("import jax, numpy as np\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x * np.float64(2.0)\n")
    assert ("dtype-promotion", 4) in _rules(src)


def test_lint_flags_default_numpy_ctor_and_dtype_kwarg():
    src = ("import jax, numpy as np\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    c = np.ones(4)\n"
           "    d = np.zeros(4, dtype=np.float32)\n"
           "    return x + c + d.sum() + x.astype('float32').sum()\n")
    rules = _rules(src)
    assert ("dtype-promotion", 4) in rules          # default-f64 ctor
    assert ("dtype-promotion", 5) not in rules      # explicit f32 is fine
    src64 = src.replace("np.float32", "np.float64")
    assert ("dtype-promotion", 5) in _rules(src64)


def test_lint_dtype_pragma_and_host_scope():
    dev = ("import jax, numpy as np\n"
           "@jax.jit\n"
           "def f(x):\n"
           "    return x * np.float64(2.0)  # lint: dtype-ok\n")
    assert not _rules(dev)
    host = ("import numpy as np\n"
            "def f(x):\n"
            "    return x * np.float64(2.0)\n")
    assert not _rules(host)                         # host code may use f64
