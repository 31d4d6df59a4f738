"""OpTest harness: numpy-reference forward checks + finite-difference
gradient checks for single ops.

Parity: reference python/paddle/fluid/tests/unittests/op_test.py
(check_output :368, check_grad :532, get_numeric_gradient :45) -- the
single most load-bearing test asset of the reference (SURVEY.md §4.1).
A subclass declares op_type/inputs/outputs/attrs; check_output runs the
op through a real Executor-compiled program; check_grad compares the
registered grad op against central finite differences.
"""
from __future__ import annotations

import unittest
from typing import Dict

import numpy as np

import paddle_tpu as fluid
from paddle_tpu.core.program import Operator, grad_var_name
from paddle_tpu.core.registry import make_grad_ops, run_op
from paddle_tpu.core.types import as_datatype


class OpTest(unittest.TestCase):
    op_type: str = None
    inputs: Dict = {}
    outputs: Dict = {}
    attrs: Dict = {}

    def setUp(self):
        import paddle_tpu.core.program as prog_mod
        from paddle_tpu import unique_name

        prog_mod._main_program = fluid.Program()
        prog_mod._startup_program = fluid.Program()
        fluid._reset_global_scope()
        unique_name.switch()
        np.random.seed(90)
        fluid.seed(90)

    # ------------------------------------------------------------------
    def _build(self):
        prog = fluid.Program()
        block = prog.global_block
        feed = {}
        input_names = {}
        for slot, val in self.inputs.items():
            entries = val if isinstance(val, list) else [(slot, val)]
            names = []
            for name, arr in entries:
                arr = np.asarray(arr)
                block.create_var(name=name, shape=arr.shape,
                                 dtype=str(arr.dtype), is_data=True,
                                 stop_gradient=False)
                feed[name] = arr
                names.append(name)
            input_names[slot] = names
        out_names = {}
        for slot, val in self.outputs.items():
            if isinstance(val, list):
                names = [n for n, _ in val]
            else:
                names = [slot]
            for n in names:
                block.create_var(name=n)
            out_names[slot] = names
        block.append_op(self.op_type, input_names, out_names, self.attrs)
        return prog, feed, out_names

    def check_output(self, atol=1e-5, rtol=1e-5, no_check_set=()):
        prog, feed, out_names = self._build()
        exe = fluid.Executor()
        fetch = []
        expect = []
        for slot, val in self.outputs.items():
            if slot in no_check_set:
                continue
            entries = val if isinstance(val, list) else [(slot, val)]
            for (name, arr), fetch_name in zip(entries, out_names[slot]):
                fetch.append(fetch_name)
                expect.append(np.asarray(arr))
        got = exe.run(prog, feed=feed, fetch_list=fetch)
        for g, e, name in zip(got, expect, fetch):
            np.testing.assert_allclose(
                np.asarray(g, dtype=np.float64),
                np.asarray(e, dtype=np.float64),
                atol=atol, rtol=rtol,
                err_msg=f"{self.op_type}: output {name} mismatch")

    # ------------------------------------------------------------------
    def check_grad(self, inputs_to_check, output_name,
                   max_relative_error=0.005, delta=5e-3,
                   no_grad_set=frozenset()):
        """Analytic grad (via the registered grad op) vs central finite
        differences of the forward kernel, like op_test.py:45.
        Runs under x64 so the fd quotient is not drowned by fp32 noise
        (the reference computes numeric grads in float64 too)."""
        import jax

        with jax.enable_x64():
            self._check_grad_impl(inputs_to_check, output_name,
                                  max_relative_error, delta, no_grad_set)

    def _check_grad_impl(self, inputs_to_check, output_name,
                         max_relative_error, delta, no_grad_set):
        prog, feed, out_names = self._build()
        feed = {k: (v.astype("float64")
                    if np.issubdtype(np.asarray(v).dtype, np.floating)
                    else v) for k, v in feed.items()}
        block = prog.global_block
        op = block.ops[-1]

        def run_forward(feed_vals):
            env = dict(feed_vals)
            import jax

            rng = [__import__("jax").random.PRNGKey(90)]
            run_op(op, env, rng_cell=rng, rng_salt=0)
            return env

        # analytic gradients: seed d(output)=1/N (mean-style reduction to
        # scalar for a well-defined scalar objective)
        out_var = output_name
        env = run_forward({k: np.asarray(v) for k, v in feed.items()})
        out_val = np.asarray(env[out_var])
        scale = 1.0 / out_val.size

        grad_ops = make_grad_ops(op, no_grad_set=no_grad_set)
        genv = dict(env)
        genv[grad_var_name(out_var)] = np.full_like(
            out_val, scale, dtype=out_val.dtype)
        # zero grads for other outputs
        for slot, names in op.outputs.items():
            for n in names:
                gname = grad_var_name(n)
                if gname not in genv:
                    genv[gname] = np.zeros_like(np.asarray(env[n]))
        import jax

        for gop in grad_ops:
            run_op(gop, genv, rng_cell=[jax.random.PRNGKey(90)],
                   rng_salt=0)

        import jax
        import jax.numpy as jnp

        for in_name in inputs_to_check:
            analytic = np.asarray(genv[grad_var_name(in_name)])
            base = np.asarray(feed[in_name], dtype=np.float64)
            others = {k: np.asarray(v) for k, v in feed.items()}

            def objective(xp):
                out = run_forward({**others, in_name: xp})[out_var]
                return jnp.sum(out, dtype=jnp.float64) * scale

            n = base.size
            eye = (jnp.eye(n, dtype=jnp.float64) * delta).reshape(
                (n,) + base.shape)
            hi = jax.jit(jax.vmap(lambda e: objective(base + e)))(eye)
            lo = jax.jit(jax.vmap(lambda e: objective(base - e)))(eye)
            numeric = np.asarray((hi - lo) / (2 * delta)).reshape(
                base.shape)
            abs_err = np.abs(analytic.astype(np.float64) - numeric)
            denom = np.maximum(np.maximum(np.abs(analytic), np.abs(
                numeric)), 1e-3)
            rel = (abs_err / denom).max()
            self.assertLessEqual(
                rel, max_relative_error,
                msg=f"{self.op_type}: grad mismatch for {in_name}: "
                    f"max rel err {rel}")
