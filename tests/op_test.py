"""OpTest harness.

Parity: reference python/paddle/fluid/tests/unittests/op_test.py:113 — a test
declares op_type, numpy inputs/attrs and expected outputs; the harness builds
a one-op program, checks outputs, and checks the emitted grad ops against
numeric finite differences of the forward program (get_numeric_gradient:40).

Place sweep parity (reference op_test.py:261 check_output_with_place, :320
check_output iterating CPUPlace + CUDAPlace): ``check_output`` always checks
on CPUPlace; when the env var ``TPU_OPTEST=1`` is set it additionally runs
the same program on ``fluid.TPUPlace()`` (needs a process that holds a
chip) and holds it to the same tolerances.  ``tools/tpu_optest.py`` drives the full
registry sweep on top of the same harness (CPU result as the oracle).
"""
from __future__ import annotations

import os

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.core.lod import LoDTensor
from paddle_tpu.core.scope import Scope


def places_to_check():
    """CPUPlace always; TPUPlace too when the sweep is enabled via env."""
    places = [fluid.CPUPlace()]
    if os.environ.get("TPU_OPTEST") == "1":
        places.append(fluid.TPUPlace())
    return places


class OpTest:
    """Subclass sets: op_type, inputs {slot: array or [(name, array), ...]},
    attrs, outputs {slot: expected or [(name, expected), ...]}.
    Inputs may be LoDTensor (fed with lod preserved, var gets lod_level)."""

    op_type = None
    inputs = {}
    attrs = {}
    outputs = {}

    # --- program construction ---
    def _build(self, extra_fetch=()):
        main = fluid.Program()
        startup = fluid.Program()
        feed = {}
        fetches = []
        with fluid.program_guard(main, startup), fluid.unique_name.guard():
            block = main.global_block()
            in_map = {}
            for slot, val in self.inputs.items():
                entries = val if isinstance(val, list) else [(slot, val)]
                names = []
                for name, arr in entries:
                    if isinstance(arr, LoDTensor):
                        block.create_var(name=name, shape=arr.shape,
                                         dtype=arr.dtype,
                                         lod_level=arr.lod_level(),
                                         stop_gradient=False)
                        feed[name] = arr
                    else:
                        arr = np.asarray(arr)
                        block.create_var(name=name, shape=arr.shape,
                                         dtype=arr.dtype, stop_gradient=False)
                        feed[name] = arr
                    names.append(name)
                in_map[slot] = names
            out_map = {}
            self._expected = {}
            for slot, val in self.outputs.items():
                entries = val if isinstance(val, list) else [(slot, val)]
                names = []
                for name, arr in entries:
                    arr = np.asarray(arr)
                    block.create_var(name=name, shape=arr.shape,
                                     dtype=arr.dtype)
                    names.append(name)
                    self._expected[name] = arr
                out_map[slot] = names
            block.append_op(type=self.op_type, inputs=in_map,
                            outputs=out_map, attrs=dict(self.attrs),
                            infer_shape=False)
        return main, startup, feed

    def run_outputs(self, place, fetch_names=None):
        """Run the one-op program on `place`; returns {name: np.ndarray}."""
        main, startup, feed = self._build()
        # kept for the abstract-shape parity property (check_output)
        self._main_for_parity = main
        self._feed_for_parity = feed
        exe = fluid.Executor(place)
        scope = Scope()
        with fluid.scope_guard(scope):
            fetch_names = list(fetch_names or self._expected.keys())
            outs = exe.run(main, feed=feed, fetch_list=fetch_names)
        return {n: np.asarray(v) for n, v in zip(fetch_names, outs)}

    def check_output_with_place(self, place, atol=1e-5, rtol=1e-5):
        """Reference op_test.py:261 — check outputs on one specific place."""
        got_map = self.run_outputs(place)
        for name, got in got_map.items():
            want = self._expected[name]
            np.testing.assert_allclose(
                np.asarray(got, dtype=np.float64),
                np.asarray(want, dtype=np.float64),
                atol=atol, rtol=rtol,
                err_msg="op %s output %s mismatch on %r" % (
                    self.op_type, name, place))
        return got_map

    # opt-out for specs whose outputs are legitimately data-dependent
    check_abstract_parity = True

    def check_abstract_parity_against(self, got_map):
        """Property: the program verifier's abstract shape inference
        (registered infer_shape or the jax.eval_shape fallback — the
        same path paddle_tpu/analysis' shape checker walks) must agree
        with the concrete output shapes/dtypes this spec just produced,
        so checker and runtime cannot drift.  Specs abstract evaluation
        cannot model are skipped (the checker downgrades those to notes,
        never errors); LoD specs are skipped because the runtime pads
        ragged feeds to bucketed shapes the declared desc does not
        carry."""
        if not self.check_abstract_parity:
            return
        for val in self._feed_for_parity.values():
            if isinstance(val, LoDTensor) and val.lod:
                return
        from paddle_tpu.analysis.shapes import canon_dtype as canon
        from paddle_tpu.core import lowering

        main = self._main_for_parity
        block = main.desc.blocks[0]
        op = block.ops[0]
        try:
            inferred = lowering.infer_op_outputs(main.desc, block, op)
        except Exception:
            return  # unmodelable: the checker reports a note, not an error
        for name, (shape, dtype) in inferred.items():
            got = got_map.get(name)
            if got is None:
                continue
            concrete = np.asarray(got)
            assert len(shape) == concrete.ndim and all(
                d == -1 or int(d) == int(c)
                for d, c in zip(shape, concrete.shape)), (
                "op %s output %s: abstract shape %s != concrete %s — "
                "the verifier's shape checker has drifted from the "
                "runtime" % (self.op_type, name, tuple(shape),
                             concrete.shape))
            assert canon(dtype) == canon(concrete.dtype), (
                "op %s output %s: abstract dtype %s != concrete %s"
                % (self.op_type, name, np.dtype(dtype), concrete.dtype))

    def check_output(self, atol=1e-5, rtol=1e-5):
        """Reference op_test.py:320 — sweep all available places; on the
        CPU place additionally hold abstract shape inference to the
        concrete outputs (see check_abstract_parity_against)."""
        for place in places_to_check():
            got_map = self.check_output_with_place(place, atol=atol,
                                                   rtol=rtol)
            if isinstance(place, fluid.CPUPlace):
                self.check_abstract_parity_against(got_map)

    # --- gradient check ---
    def check_grad(self, inputs_to_check, output_names=None,
                   max_relative_error=0.005, delta=1e-3):
        """Analytic grads (append_backward over the one-op program) vs
        numeric finite differences of a scalar head: sum(out * W) with fixed
        random W per output.  With TPU_OPTEST=1, additionally holds the
        TPU-place analytic grads to the CPU-place analytic grads (the CPU
        grads being the finite-difference-validated oracle)."""
        output_names = output_names or [
            n for n in self._first_float_outputs()]
        main, startup, feed = self._build()
        rng = np.random.RandomState(7)
        weights = {}
        with fluid.program_guard(main, startup):
            block = main.global_block()
            parts = []
            for oname in output_names:
                ovar = block.var(oname)
                w = rng.uniform(0.5, 1.5,
                                [int(d) for d in ovar.shape]).astype(
                                    np.float32)
                weights[oname] = w
                wvar = fluid.layers.assign(w)
                wvar.stop_gradient = True
                prod = fluid.layers.elementwise_mul(ovar, wvar)
                parts.append(fluid.layers.reduce_sum(prod))
            head = parts[0] if len(parts) == 1 else fluid.layers.sums(parts)
            loss = fluid.layers.reduce_sum(head)
            grads = fluid.backward.calc_gradient(
                loss, [block.var(n) for n in inputs_to_check])
        executors = {}   # one Executor per place: its jit cache is
                         # per-instance, and the FD loop re-runs the
                         # same program hundreds of times

        def run_fetch(names, feed_over=None, place=None):
            f = dict(feed)
            if feed_over:
                f.update(feed_over)
            place = place or fluid.CPUPlace()
            exe = executors.setdefault(place, fluid.Executor(place))
            scope = Scope()
            with fluid.scope_guard(scope):
                return exe.run(main, feed=f, fetch_list=names)

        grad_names = [g.name for g in grads]
        analytic = run_fetch(grad_names)

        for iname, a_grad in zip(inputs_to_check, analytic):
            x = np.asarray(feed[iname], dtype=np.float64)
            num = np.zeros_like(x)
            flat = x.reshape(-1)
            for i in range(flat.size):
                for sgn, store in ((1, "p"), (-1, "m")):
                    pert = flat.copy()
                    pert[i] += sgn * delta
                    out = run_fetch([loss.name],
                                    {iname: pert.reshape(x.shape).astype(
                                        feed[iname].dtype)})
                    if sgn == 1:
                        fp = float(np.asarray(out[0]).reshape(-1)[0])
                    else:
                        fm = float(np.asarray(out[0]).reshape(-1)[0])
                num.reshape(-1)[i] = (fp - fm) / (2 * delta)
            a = np.asarray(a_grad, dtype=np.float64)
            # normalize by the LARGEST gradient magnitude: fp32 forward +
            # finite differences put an absolute noise floor on every
            # element, so per-element relative error is meaningless for
            # near-zero entries (reference op_test.py __assert_is_close
            # uses the same idea)
            scale_ = max(np.abs(a).max(), np.abs(num).max(), 1e-3)
            rel = np.abs(a - num) / scale_
            assert rel.max() <= max_relative_error, (
                "op %s grad wrt %s: max rel err %.5f (analytic %s vs "
                "numeric %s)" % (self.op_type, iname, rel.max(),
                                 a.reshape(-1)[:5], num.reshape(-1)[:5]))

        # Cross-place grad check: device analytic grads vs the CPU analytic
        # grads just validated above (reference check_grad_with_place role).
        for place in places_to_check()[1:]:
            dev = run_fetch(grad_names, place=place)
            for iname, a_grad, d_grad in zip(inputs_to_check, analytic, dev):
                a = np.asarray(a_grad, dtype=np.float64)
                d = np.asarray(d_grad, dtype=np.float64)
                scale_ = max(np.abs(a).max(), 1e-3)
                rel = np.abs(a - d) / scale_
                assert rel.max() <= max_relative_error, (
                    "op %s grad wrt %s: CPU vs %r max rel err %.5f" %
                    (self.op_type, iname, place, rel.max()))

    def _first_float_outputs(self):
        names = []
        for slot, val in self.outputs.items():
            entries = val if isinstance(val, list) else [(slot, val)]
            for name, arr in entries:
                if np.issubdtype(np.asarray(arr).dtype, np.floating):
                    names.append(name)
        return names
