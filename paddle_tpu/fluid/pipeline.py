"""Pipeline parallelism from the fluid front-end.

The pp axis was previously reachable only through the raw-JAX GPipe
utility (parallel/pipeline.py, homogeneous stages); this module makes a
fluid Program pipeline-parallel: split the global block at user-chosen
cut variables, place each stage's ops + parameters on its own device,
and run a GPipe schedule (all microbatch forwards, then reversed
backwards, grads accumulated) with per-stage jitted functions whose
async dispatch overlaps across devices.

No reference analog exists (pipeline arrived after the snapshot); this
is a beyond-reference axis like sp/ep, SURVEY §2.5 row 52.
"""
from __future__ import annotations

import numpy as np

__all__ = ["PipelineProgram"]


class _Stage:
    __slots__ = ("ops", "param_names", "in_act", "out_act", "device",
                 "fn", "feed_reads")

    def __init__(self, ops, param_names, in_act, out_act, device):  # noqa: D401
        self.ops = ops
        self.param_names = param_names
        self.in_act = in_act       # activation inputs (from prev stage)
        self.out_act = out_act     # activation outputs (to next stage)
        self.device = device
        self.fn = None


class PipelineProgram:
    """Split ``program`` into len(cut_vars)+1 stages at the given
    variable names; stage i runs on devices[i].

    The last stage must compute ``loss``.  Feeds enter stage 0;
    parameters stay resident on their stage's device.  If the program
    contains optimizer ops (``optimizer.minimize`` ran on it), each
    ``train_step`` applies THOSE per stage — Adam trains as Adam; a
    program without optimizer ops uses the explicit ``lr`` SGD instead
    (and mixing the two raises rather than silently ignoring one).
    ``sync_to_scope`` writes parameters (and optimizer accumulators)
    back.
    """

    @classmethod
    def from_annotations(cls, program, loss, devices, scope, feed_names):
        """The spmd route (ISSUE 20): lower a program whose ops carry
        ``__pp_stage__`` tags (written by
        ``paddle_tpu.parallel.spmd.assign_pipeline_stages`` / a
        pp-bearing placement) instead of hand-picked cut vars — the
        stage boundaries and cut activations are recovered from the
        annotations, so the pipeline carrier consumes the same
        annotated-program contract as the GSPMD executor path."""
        from paddle_tpu.parallel.spmd import PP_STAGE_ATTR
        from .framework import OpRole

        block = program.global_block()
        ops = [op for op in block.desc.ops
               if op.type not in ("feed", "fetch")
               and not (op.role & (OpRole.Backward | OpRole.Optimize))]
        tagged = [(op, op.attr(PP_STAGE_ATTR)) for op in ops]
        if any(s is None for _, s in tagged):
            raise ValueError(
                "program has untagged ops; run "
                "spmd.assign_pipeline_stages(program, n_stages) first")
        n_stages = max(s for _, s in tagged) + 1
        if n_stages != len(devices):
            raise ValueError("%d annotated stages but %d devices"
                             % (n_stages, len(devices)))
        cut_vars = []
        for s in range(n_stages - 1):
            here = [op for op, st in tagged if st == s]
            later_reads = {n for op, st in tagged if st > s
                           for n in op.input_arg_names()}
            crossing = [n for op in here
                        for n in op.output_arg_names()
                        if n in later_reads and not scope.has_var(n)]
            if not crossing:
                raise ValueError(
                    "no activation crosses the stage %d/%d boundary"
                    % (s, s + 1))
            cut_vars.append(crossing[-1])
        return cls(program, loss, cut_vars, devices, scope, feed_names)

    def __init__(self, program, loss, cut_vars, devices, scope,
                 feed_names):
        import jax

        self.program = program
        self.loss_name = loss if isinstance(loss, str) else loss.name
        self.feed_names = list(feed_names)
        cut_names = [v if isinstance(v, str) else v.name
                     for v in cut_vars]
        if len(devices) != len(cut_names) + 1:
            raise ValueError(
                "%d cut vars make %d stages but %d devices given" %
                (len(cut_names), len(cut_names) + 1, len(devices)))
        self.stages = self._split(program, cut_names, devices, scope)
        for st in self.stages:
            st.fn = self._build_stage_fn(st)
            # static per-stage feed consumption (hot path reads it)
            st_ops_inputs = {n for op in st.ops
                             for n in op.input_arg_names()}
            st.feed_reads = sorted(set(self.feed_names) & st_ops_inputs)
        self._rng_counter = 0
        # parameters resident per stage device
        self.params = [
            {n: jax.device_put(np.asarray(scope.find_var(n)), st.device)
             for n in st.param_names}
            for st in self.stages]
        self._collect_optimizer_ops(program, scope)
        # join the prepared-execution flush protocol: any read path on
        # this scope — Executor.run, io save/checkpoint, Scope.find_var
        # — flushes the stage-resident params/optimizer state back
        # first.  Register on every scope that OWNS one of our names
        # too: a reader rooted at the owning ancestor never walks down
        # to the construction scope.
        self.scope = scope
        self._dirty = False
        owners = {id(scope): scope}
        names = [n for st in self.stages for n in st.param_names]
        names += [n for st_state in self._opt_state for n in st_state]
        for n in names:
            s = scope.find_scope_of(n)
            if s is not None:
                owners.setdefault(id(s), s)
        for s in owners.values():
            s.attach_prepared(self)
        # per-name write-version baselines: an EXTERNAL write (a
        # checkpoint load, a user scope.set) always wins over the
        # stage-resident copy — detected exactly like PreparedProgram
        self._seen = {}
        for n in names:
            self._record_seen(n)

    def _record_seen(self, name):
        from paddle_tpu.core.executor_impl import seen_entry

        self._seen[name] = seen_entry(self.scope, name)

    def _external_writes(self):
        """Names written in the scope since we last read/installed
        them."""
        from paddle_tpu.core.executor_impl import seen_changed

        return {n for n, seen in self._seen.items()
                if seen_changed(self.scope, n, seen)}

    def _restage_external(self):
        """Pull externally written params/optimizer state back onto the
        stage devices (scope wins)."""
        import jax

        ext = self._external_writes()
        if not ext:
            return
        for i, st in enumerate(self.stages):
            for part in (self.params[i], self._opt_state[i]):
                for n in part:
                    if n in ext:
                        part[n] = jax.device_put(
                            np.asarray(self.scope.find_var(n)),
                            st.device)
                        self._record_seen(n)

    def _collect_optimizer_ops(self, program, scope):
        """Assign the program's optimizer ops (and their accumulator /
        LR state) to the stage owning their Param; refuse programs with
        global optimizer-role ops (LR schedules &c.) loudly — running a
        pipelined program with a silently-dropped schedule would train
        wrong."""
        import jax

        from .framework import OpRole

        block = program.global_block()
        opt_ops = [op for op in block.desc.ops
                   if op.role & OpRole.Optimize]
        self._opt_ops = [[] for _ in self.stages]
        self._opt_state = [{} for _ in self.stages]
        self.has_program_optimizer = bool(opt_ops)
        if not opt_ops:
            return
        nonparam = [op.type for op in opt_ops
                    if not (op.inputs.get("Param") or [None])[0]]
        if nonparam:
            raise NotImplementedError(
                "pipeline: program has global optimizer-role ops %r "
                "(e.g. an LR schedule) that have no owning stage" %
                nonparam)
        owner = {n: i for i, st in enumerate(self.stages)
                 for n in st.param_names}
        for op in opt_ops:
            pname = op.inputs["Param"][0]
            if pname not in owner:
                raise ValueError(
                    "optimizer op %r updates %r which no stage owns"
                    % (op.type, pname))
            self._opt_ops[owner[pname]].append(op)
        for i, st in enumerate(self.stages):
            state_names = sorted({
                n for op in self._opt_ops[i]
                for slot, ns in op.inputs.items()
                for n in ns
                if slot not in ("Param", "Grad") and n})
            self._opt_state[i] = {
                n: jax.device_put(np.asarray(scope.find_var(n)),
                                  st.device)
                for n in state_names}

    def _apply_program_optimizer(self, grads):
        """Run each stage's optimizer ops on its device: env carries
        params + accumulators, Grad slots get the accumulated pipeline
        grads, and fluid's in-place contract (ParamOut/MomentOut alias
        the input names) hands back the updated state."""
        import jax

        from paddle_tpu.core.lowering import LoweringContext, run_op

        desc = self.program.desc
        for i, st in enumerate(self.stages):
            if not self._opt_ops[i]:
                continue
            env = dict(self.params[i])
            env.update(self._opt_state[i])
            for op in self._opt_ops[i]:
                pn = op.inputs["Param"][0]
                gn = op.inputs["Grad"][0]
                g = grads[i].get(pn)
                env[gn] = (g if g is not None
                           else jax.numpy.zeros_like(env[pn]))
            ctx = LoweringContext(desc, 0, env, jax.random.PRNGKey(0),
                                  mode="train")
            ctx.block = desc.blocks[0]
            for op in self._opt_ops[i]:
                run_op(ctx, op)
            self.params[i] = {n: env[n] for n in self.params[i]}
            self._opt_state[i] = {n: env[n] for n in self._opt_state[i]}

    # ------------------------------------------------------------------
    def _split(self, program, cut_names, devices, scope):
        block = program.global_block()
        ops = [op for op in block.desc.ops
               if op.type not in ("feed", "fetch")]
        # drop backward/optimize ops: the pipeline drives its own vjp
        from .framework import OpRole
        ops = [op for op in ops
               if not (op.role & (OpRole.Backward | OpRole.Optimize))]

        stages = []
        bounds = []
        cut_left = list(cut_names)
        for idx, op in enumerate(ops):
            outs = set(op.output_arg_names())
            if cut_left and cut_left[0] in outs:
                bounds.append(idx + 1)
                cut_left.pop(0)
        if cut_left:
            raise ValueError("cut vars %r are not produced by the "
                             "program" % cut_left)
        bounds = [0] + bounds + [len(ops)]
        for i in range(len(bounds) - 1):
            seg = ops[bounds[i]:bounds[i + 1]]
            writes = {n for op in seg for n in op.output_arg_names()
                      if n}
            reads = {n for op in seg for n in op.input_arg_names()
                     if n and n not in writes}
            params = sorted(n for n in reads if scope.has_var(n))
            in_act = sorted(n for n in reads
                            if not scope.has_var(n))
            stages.append(_Stage(seg, params, in_act, None,
                                 devices[i]))
        # frozen parameters are vjp'd through but never updated
        blk_vars = program.global_block().vars
        self._frozen = {
            n for st in stages for n in st.param_names
            if n in blk_vars and not getattr(blk_vars[n], "trainable",
                                             True)}
        # activation outputs: what later stages (or the loss) read.
        # Skip connections (an activation read by a NON-adjacent stage)
        # would need cotangent forwarding through the middle stages —
        # unsupported; fail at construction, not with wrong gradients.
        for i, st in enumerate(stages):
            produced_here = {n for op in st.ops
                             for n in op.output_arg_names() if n}
            for k in range(i + 2, len(stages)):
                skip = produced_here & set(stages[k].in_act)
                if skip:
                    raise ValueError(
                        "activation(s) %r of stage %d are read by "
                        "non-adjacent stage %d; move the cut so every "
                        "activation flows only to the next stage" %
                        (sorted(skip), i, k))
            needed = set([self.loss_name]) if i == len(stages) - 1 \
                else set()
            if i + 1 < len(stages):
                needed |= set(stages[i + 1].in_act)
            st.out_act = sorted(n for n in produced_here if n in needed)
        return stages

    def _build_stage_fn(self, st):
        import jax

        from paddle_tpu.core.lowering import LoweringContext, run_op
        from paddle_tpu.core.place import placed_on

        program_desc = self.program.desc
        ops = list(st.ops)
        out_names = list(st.out_act)

        def fn(params, acts, rng_counter):
            env = dict(params)
            env.update(acts)
            # fresh key per (step, microbatch): stochastic ops (dropout)
            # must not repeat their masks across microbatches or steps
            key = jax.random.fold_in(jax.random.PRNGKey(0), rng_counter)
            ctx = LoweringContext(program_desc, 0, env, key, "train")
            with placed_on(st.device):
                for op in ops:
                    run_op(ctx, op)
            return {n: env[n] for n in out_names}

        # placement follows the stage's device_put inputs (params and
        # activations are committed to st.device before each call)
        return jax.jit(fn)

    # ------------------------------------------------------------------
    def train_step(self, feed, n_microbatches, lr=None):
        """One GPipe step: split the feed on dim 0 into microbatches,
        forward all of them through the stages (async dispatch overlaps
        stages across devices), then backward in reverse, accumulate
        per-stage grads, apply the update.  Returns the mean microbatch
        loss.

        Update source: the program's own optimizer ops when present
        (``lr`` must then be None); otherwise plain SGD with ``lr``."""
        import jax

        if self.has_program_optimizer:
            if lr is not None:
                raise ValueError(
                    "program has optimizer ops (minimize ran on it) — "
                    "drop lr=...: train_step applies the program's "
                    "optimizer, the manual-SGD lr would be ignored")
        elif lr is None:
            raise ValueError(
                "program has no optimizer ops: pass lr= for the "
                "manual-SGD update (or run optimizer.minimize on it)")

        # external scope writes (load_persistables, user scope.set)
        # since the last step/sync win over stage-resident copies
        self._restage_external()
        mbs = self._split_feed(feed, n_microbatches)
        # forward: keep vjp closures per (stage, microbatch)
        vjps = [[None] * len(self.stages) for _ in mbs]
        losses = []
        for m, mb in enumerate(mbs):
            acts = {k: jax.device_put(v, self.stages[0].device)
                    for k, v in mb.items()}
            self._rng_counter += 1
            counter = self._rng_counter
            for i, st in enumerate(self.stages):
                stage_in = {n: acts[n] for n in st.in_act
                            if n in acts}
                stage_in.update({k: acts[k] for k in st.feed_reads
                                 if k in acts})
                # every input committed to this stage's device (feeds
                # arrive on stage 0's; activations on the previous)
                stage_in = {k: jax.device_put(v, st.device)
                            for k, v in stage_in.items()}
                outs, vjp = jax.vjp(
                    lambda p, a, f=st.fn, c=counter: f(p, a, c),
                    self.params[i], stage_in)
                vjps[m][i] = vjp
                nxt_dev = (self.stages[i + 1].device
                           if i + 1 < len(self.stages) else None)
                acts = dict(acts)
                for k, v in outs.items():
                    acts[k] = (jax.device_put(v, nxt_dev)
                               if nxt_dev is not None else v)
            losses.append(acts[self.loss_name])

        # backward (reverse microbatch order, GPipe drain) + accumulate
        grads = [None] * len(self.stages)
        for m in reversed(range(len(mbs))):
            cot = {self.loss_name:
                   jax.numpy.ones_like(losses[m]) / len(mbs)}
            for i in reversed(range(len(self.stages))):
                st = self.stages[i]
                # every out_act flows to the adjacent consumer (checked
                # at construction), so all cotangents are present
                full_cot = {n: cot[n] for n in st.out_act}
                gp, ga = vjps[m][i](full_cot)
                grads[i] = gp if grads[i] is None else \
                    jax.tree_util.tree_map(jax.numpy.add, grads[i], gp)
                cot = {k: jax.device_put(
                    v, self.stages[i - 1].device if i else st.device)
                    for k, v in ga.items()}
        if self.has_program_optimizer:
            self._apply_program_optimizer(grads)
        else:
            # SGD in place, per stage on its device (frozen skipped)
            for i, st in enumerate(self.stages):
                self.params[i] = {
                    n: (self.params[i][n] if n in self._frozen
                        else self.params[i][n] - lr * grads[i][n])
                    for n in self.params[i]}
        self._dirty = True
        return float(np.mean([np.asarray(l).ravel()[0]
                              for l in losses]))

    def _split_feed(self, feed, n):
        out = [dict() for _ in range(n)]
        for k, v in feed.items():
            v = np.asarray(v)
            if v.shape[0] % n:
                raise ValueError(
                    "batch dim %d of %r does not divide into %d "
                    "microbatches" % (v.shape[0], k, n))
            for m, part in enumerate(np.split(v, n, axis=0)):
                out[m][k] = part
        return out

    def sync_to_scope(self, scope):
        for st_params in self.params:
            for n, v in st_params.items():
                (scope.find_scope_of(n) or scope).set(n, np.asarray(v))
        for st_state in self._opt_state:
            for n, v in st_state.items():
                (scope.find_scope_of(n) or scope).set(n, np.asarray(v))
        if scope is self.scope:
            for part in self.params + self._opt_state:
                for n in part:
                    self._record_seen(n)
            self._dirty = False

    def sync_scope(self):
        """flush_prepared protocol entry point (core/executor_impl):
        write stage-resident params + optimizer state back to the
        construction scope — except names written EXTERNALLY since we
        last read them (a checkpoint load mid-training): those keep the
        scope's newer value and are re-staged at the next train_step."""
        ext = self._external_writes()
        scope = self.scope
        for part in self.params + self._opt_state:
            for n, v in part.items():
                if n in ext:
                    continue
                s = scope.find_scope_of(n) or scope
                s.set(n, np.asarray(v))
                self._seen[n] = (s, s._write_versions[n])
        self._dirty = False
