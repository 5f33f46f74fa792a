"""The fused SLO-composite decision's packed calling convention.

``fused_composite_decide`` packs its twelve host operands into one
float32 buffer, runs one jitted program on it and copies one (2, F)
result back.  These tests pin that the packing changes no answer — the
choices equal the NumPy oracle (``SLOCompositePolicy.cascade`` + argmin)
and, bit for bit, a jnp copy of the twelve-operand body — and that one
array goes in and one comes out.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.scheduler import SLOCompositePolicy
from repro.kernels import policy_score as ps

FS = [1, 3, 4, 9]
PS = [1, 5, 128, 300]


@jax.jit
def _twelve_operands(ewma_v, ewma_n, analytic_s, resp_h2, resp_n, data_s,
                     nodes, loaded_w, alive, unloaded, slo_s, energy_weight):
    """The decision as one jit over twelve separate operands."""
    exec_s = jnp.where(ewma_n >= 3, ewma_v, analytic_s)
    p90_s = jnp.where(resp_n >= 10, resp_h2, exec_s * 1.5)
    energy_j = (exec_s * nodes[None, :]) * loaded_w[None, :]
    ok = alive & unloaded[None, :]
    ok = jnp.where(ok.any(axis=1, keepdims=True), ok, alive)
    feasible = ok & (p90_s <= slo_s[:, None])
    feasible = jnp.where(feasible.any(axis=1, keepdims=True), feasible, ok)
    cost = (exec_s + data_s) + energy_weight * energy_j
    masked = jnp.where(feasible, cost, jnp.inf)
    finite = jnp.isfinite(masked)
    masked = jnp.where(finite, masked, jnp.inf)
    return (jnp.argmin(masked, axis=1).astype(jnp.int32),
            finite.any(axis=1))


def _operands(f, p, wide, loaded, seed=0):
    """Dyadic columns (exact in float32, so the float64 oracle sees the
    same numbers), counts on both sides of the estimator gates, a dead
    first row, an SLO no platform meets on the last row, and inf data
    costs.  ``wide`` gives float64/int64 columns and a Python-float
    weight, else float32/int32 and a NumPy float32 weight; ``loaded``
    marks every platform as over its utilization threshold."""
    rng = np.random.default_rng([seed, f, p])
    fl, it = (np.float64, np.int64) if wide else (np.float32, np.int32)
    data_s = rng.integers(0, 64, (f, p)) / 8.0
    data_s[rng.random((f, p)) < 0.1] = np.inf
    alive = rng.random((f, p)) < 0.8
    alive[0] = False
    slo_s = rng.integers(1, 64, f) / 4.0
    slo_s[-1] = 0.0
    unloaded = np.zeros(p, bool) if loaded else rng.random(p) < 0.6
    weight = 0.125 if wide else np.float32(0.125)
    return dict(
        ewma_v=(rng.integers(1, 64, (f, p)) / 8.0).astype(fl),
        ewma_n=rng.choice([0, 2, 3, 5], (f, p)).astype(it),
        analytic_s=(rng.integers(1, 64, (f, p)) / 8.0).astype(fl),
        resp_h2=(rng.integers(1, 128, (f, p)) / 8.0).astype(fl),
        resp_n=rng.choice([0, 9, 10, 12], (f, p)).astype(it),
        data_s=data_s.astype(fl),
        nodes=rng.integers(1, 9, p).astype(fl),
        loaded_w=(rng.integers(1, 64, p) / 4.0).astype(fl),
        alive=alive, unloaded=unloaded, slo_s=slo_s.astype(fl),
        energy_weight=weight)


def _oracle(a):
    """``SLOCompositePolicy.cascade`` in float64, then the host argmin."""
    f64 = {k: np.asarray(v, np.float64) for k, v in a.items()
           if k not in ("alive", "unloaded")}
    exec_s = np.where(a["ewma_n"] >= 3, f64["ewma_v"], f64["analytic_s"])
    feats = {"alive": a["alive"], "exec_s": exec_s, "data_s": f64["data_s"],
             "p90_s": np.where(a["resp_n"] >= 10, f64["resp_h2"],
                               exec_s * 1.5),
             "energy_j": exec_s * f64["nodes"][None, :] *
             f64["loaded_w"][None, :],
             "cpu_util": np.where(a["unloaded"], 0.0, 1.0),
             "mem_util": np.zeros(len(a["unloaded"])),
             "slo_s": f64["slo_s"]}
    cost, kill = SLOCompositePolicy.cascade(
        feats, {"cpu_threshold": 0.9, "mem_threshold": 0.95,
                "energy_weight": float(a["energy_weight"])})
    masked = np.where(kill == 0, cost, np.inf)
    finite = np.isfinite(masked)
    return (np.argmin(np.where(finite, masked, np.inf), axis=1),
            finite.any(axis=1))


@pytest.mark.parametrize("loaded", [False, True], ids=["mixed", "loaded"])
@pytest.mark.parametrize("wide", [False, True], ids=["32bit", "64bit"])
@pytest.mark.parametrize("p", PS)
@pytest.mark.parametrize("f", FS)
def test_packed_decision_matches_oracle_and_twelve_operands(f, p, wide,
                                                            loaded):
    a = _operands(f, p, wide, loaded)
    choice, ok = ps.fused_composite_decide(**a)
    want_choice, want_ok = _oracle(a)
    np.testing.assert_array_equal(ok, want_ok)
    np.testing.assert_array_equal(choice, want_choice)
    assert not ok[0]                    # the dead row has no candidate
    twelve = _twelve_operands(**a)
    np.testing.assert_array_equal(choice, np.asarray(twelve[0]))
    np.testing.assert_array_equal(ok, np.asarray(twelve[1]))


@pytest.mark.parametrize("weight", [0.1, 0.3, 2.5])
def test_non_dyadic_operands_match_twelve_operands_bit_for_bit(weight):
    """Float64 columns that float32 rounds, and a weight that float32
    cannot hold: the packed buffer rounds them as JAX's own argument
    conversion does."""
    rng = np.random.default_rng(7)
    f, p = 4, 5
    a = _operands(f, p, wide=True, loaded=False)
    for k in ("ewma_v", "analytic_s", "resp_h2", "data_s"):
        a[k] = rng.random((f, p)) * 3.0
    for k in ("nodes", "loaded_w"):
        a[k] = rng.random(p) * 40.0
    a["slo_s"] = rng.random(f) * 5.0
    a["energy_weight"] = weight
    choice, ok = ps.fused_composite_decide(**a)
    twelve = _twelve_operands(**a)
    np.testing.assert_array_equal(choice, np.asarray(twelve[0]))
    np.testing.assert_array_equal(ok, np.asarray(twelve[1]))


@pytest.mark.parametrize("f,p", [(1, 1), (4, 5), (9, 300)])
def test_packed_kernel_has_one_operand_and_one_result(f, p):
    words = ps.packed_words(f, p)
    assert words == 7 * f * p + 3 * p + f + 1
    lowered = ps._fused_composite_decide_packed.lower(
        jax.ShapeDtypeStruct((words,), jnp.float32), f=f, p=p)
    (args, kwargs) = lowered.in_avals
    assert kwargs == {}
    assert [(x.shape, x.dtype) for x in args] == [((words,), jnp.float32)]
    out = lowered.out_info
    assert (out.shape, out.dtype) == ((2, f), jnp.int32)
    assert "fused_composite_decide" in lowered.as_text().split("\n")[0]


@pytest.fixture
def packed_calls(monkeypatch):
    """(buffer, static kwargs, result) of every packed kernel call."""
    calls = []
    packed = ps._fused_composite_decide_packed

    def spy(buf, **kw):
        out = packed(buf, **kw)
        calls.append((buf, kw, out))
        return out

    monkeypatch.setattr(ps, "_fused_composite_decide_packed", spy)
    return calls


@pytest.mark.parametrize("f,p", [(1, 5), (4, 5), (3, 128)])
def test_one_buffer_in_and_host_arrays_out(packed_calls, f, p):
    choice, ok = ps.fused_composite_decide(
        **_operands(f, p, wide=True, loaded=False))
    ((buf, kw, out),) = packed_calls
    assert type(buf) is np.ndarray and buf.dtype == np.float32
    assert buf.shape == (ps.packed_words(f, p),) and kw == {"f": f, "p": p}
    assert out.shape == (2, f)
    for arr, dtype in ((choice, np.int32), (ok, np.bool_)):
        assert type(arr) is np.ndarray
        assert arr.dtype == dtype and arr.shape == (f,)


@pytest.mark.parametrize("wide", [False, True], ids=["32bit", "64bit"])
def test_unpacked_operands_are_what_jax_transfers(packed_calls, wide):
    """Every operand the device reads back out of the buffer equals, in
    dtype, shape and every bit, what JAX makes of that operand passed
    on its own: float64 rounded to float32, int64 counts to int32."""
    rng = np.random.default_rng(11)
    f, p = 3, 7
    a = _operands(f, p, wide, loaded=False)
    for k in ("ewma_v", "analytic_s", "resp_h2"):
        a[k] = (rng.random((f, p)) * 3.0).astype(a[k].dtype)
    a["ewma_n"][0, :2] = [2 ** 31 - 1, -5]
    a["energy_weight"] = 0.1 if wide else np.float32(0.1)
    ps.fused_composite_decide(**a)
    ((buf, _kw, _out),) = packed_calls
    unpacked = jax.jit(ps._unpack, static_argnums=(1, 2))(buf, f, p)
    names = list(inspect.signature(ps.fused_composite_decide).parameters)
    assert len(unpacked) == len(names) == 12
    for name, got in zip(names, unpacked):
        want = jnp.asarray(a[name])
        assert (got.dtype, got.shape) == (want.dtype, want.shape), name
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
