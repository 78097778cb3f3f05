"""What the gated delta rule's backward is handed (`ops/linear_attention.py`,
interpret mode): the forward's six chunk operands and T as residuals, so
the preparation (`mx_gdn_prepare`) runs once a call, against the form in
which the backward ran the preparation again, kept here as the reference.
The arithmetic and the kernels are the same, so the value and all five
gradients are equal to the bit."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import linear_attention as la


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _recomputing_rule(q, k, v, g, beta, chunk, interpret, state):
    operands = la._chunk_operands(q, k, v, g, beta, chunk, interpret, state)
    return la._scan_forward(*operands, chunk, interpret)[0]


def _recomputing_fwd(q, k, v, g, beta, chunk, interpret, state):
    operands = la._chunk_operands(q, k, v, g, beta, chunk, interpret, state)
    o, states = la._scan_forward(*operands, chunk, interpret)
    return o, (q, k, v, g, beta, states)


def _recomputing_bwd(chunk, interpret, state, res, do):
    # the operands formed again, with T, from the arguments
    q, k, v, g, beta, states = res
    *operands, inv = la._chunk_operands(q, k, v, g, beta, chunk, interpret,
                                        state, keep_inverse=True)
    cotangents = la._scan_backward(*operands, states, do.astype(q.dtype),
                                   chunk, interpret)
    return la._chunk_operands_pullback(q, k, v, g, beta, inv, cotangents,
                                       chunk, interpret, state)


_recomputing_rule.defvjp(_recomputing_fwd, _recomputing_bwd)


def _reference(q, k, v, g, beta, chunk):
    """`gated_delta_rule`'s shapes and types around the recomputing form."""
    flat = lambda x: x.reshape((-1,) + x.shape[2:])
    out = _recomputing_rule(flat(q), flat(k.astype(q.dtype)),
                            flat(v.astype(q.dtype)), flat(g), flat(beta),
                            chunk, True, jnp.dtype(la.STATE_DTYPE))
    return out.reshape(v.shape).astype(v.dtype)


def _inputs(seed, heads_k, heads, seq, dk, dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, heads_k, seq, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (1, heads_k, seq, dk)))
    v = jax.random.normal(ks[2], (1, heads, seq, dv))
    g = -0.3 * jnp.exp(jax.random.normal(ks[3], (1, heads, seq)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, heads, seq)))
    cot = jax.random.normal(ks[5], (1, heads, seq, dv)).astype(dtype)
    return [a.astype(dtype) for a in (q, k, v)] + [g, beta], cot


def _value_and_gradients(rule, args, cot):
    out, pull = jax.vjp(rule, *args)
    return (out,) + pull(cot)


def _pallas_calls(jaxpr):
    """Every `pallas_call` of a jaxpr and of the jaxprs inside it."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _pallas_calls(inner)
    return found


def _calls_by_kernel(fn, *args):
    calls = _pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    names = [eqn.params["name"] for eqn in calls]
    return {name: names.count(name) for name in set(names)}, calls


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("seq,chunk,dk,dv", [
    (64, 64, 16, 24), (128, 64, 16, 24), (16, 16, 16, 24), (48, 16, 16, 24),
    # the widths of `qwen3_next_80b_a3b`: two tiles of two chunks
    (256, 64, 128, 128)])
def test_kept_operands_give_the_recomputing_form_to_the_bit(seq, chunk, dk,
                                                            dv, dtype):
    """Value and all five gradients, one key head under two value
    heads."""
    args, cot = _inputs(seq + chunk, 1, 2, seq, dk, dv, dtype)
    rule = functools.partial(la.gated_delta_rule, chunk=chunk)
    got = jax.jit(lambda: _value_and_gradients(rule, args, cot))()
    want = jax.jit(lambda: _value_and_gradients(
        functools.partial(_reference, chunk=chunk), args, cot))()
    for name, a, b in zip(("o", "dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


@pytest.mark.parametrize("layers", [1, 3])
def test_the_preparation_runs_once_a_call_under_vjp(layers):
    """Value and gradients of `layers` calls: one `mx_gdn_prepare` a call
    (the recomputing form runs two), and one of each other kernel."""
    args, cot = _inputs(5, 1, 2, 128, 16, 24, "float32")

    def stacked(rule):
        def value(q, k, v, g, beta):
            for _ in range(layers):
                v = rule(q, k, v, g, beta)
            return v
        return lambda *a: _value_and_gradients(value, a, cot)

    counts, _ = _calls_by_kernel(
        stacked(functools.partial(la.gated_delta_rule, chunk=64)), *args)
    assert counts == {"mx_gdn_prepare": layers, "mx_gdn_fwd": layers,
                      "mx_gdn_bwd": layers, "mx_gdn_prepare_bwd": layers}
    counts, _ = _calls_by_kernel(
        stacked(functools.partial(_reference, chunk=64)), *args)
    assert counts["mx_gdn_prepare"] == 2 * layers


def test_only_a_differentiated_call_writes_t():
    """The preparation's results: six without differentiation (the
    primal, and the evaluation forward of a training run), seven under
    `jax.vjp`, the seventh T in fp32, a tile's rows wide."""
    args, cot = _inputs(7, 1, 2, 256, 16, 24, "bfloat16")
    rule = functools.partial(la.gated_delta_rule, chunk=64)
    rows = la._tile_rows(256, 64)
    for fn, written in (
            (rule, 6), (jax.jit(rule), 6),
            (lambda *a: _value_and_gradients(rule, a, cot), 7)):
        counts, calls = _calls_by_kernel(fn, *args)
        assert counts["mx_gdn_prepare"] == 1
        prepare, = [e for e in calls if e.params["name"] == "mx_gdn_prepare"]
        outs = [v.aval for v in prepare.outvars]
        assert len(outs) == written
        inverses = [a for a in outs
                    if a.dtype == jnp.float32 and a.shape[-1] == rows
                    and a.shape[-2] == 256]
        assert len(inverses) == written - 6
