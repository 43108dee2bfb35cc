"""One elastic SPMD runtime: sharding-annotated programs, a measured-cost
auto-sharding search, and mid-job mesh resharding.

Every parallelism axis in this repo worked before this module — dp / tp /
sp(ring) / ep / pp are all measured in MESH_PROFILE_r06.md — but each
lived in its own carrier (ParallelExecutor meshes, fluid/pipeline.py, the
pserver transpiler, parallel/ring.py + moe.py), compositions were
hand-wired per model, and strategy choice was guesswork.  This module is
the GSPMD-style collapse (Xu et al. 2021; the reference repo's
multi_devices_graph_builder role, done on JAX/XLA):

1. :class:`ShardingPass` — a PR 3 ``ProgramPass`` that seeds and
   propagates per-VarDesc sharding annotations (``desc.var_shardings``,
   the dict the executor already lowers through jit
   in_shardings/out_shardings = GSPMD) across a whole ProgramDesc:
   forward through the op graph, mirrored onto gradients, mirrored onto
   optimizer accumulators.  One annotation carrier for dp, tp, sp, ep —
   and pp stage tags (``__pp_stage__`` op attrs) that
   ``fluid.pipeline.PipelineProgram.from_annotations`` lowers.

2. :class:`CostModel` — every cost term traceable to a measurement:
   per-kernel times from the PR 7 autotune cache, collective alpha/beta
   fitted from the MESH_PROFILE measured legs + optimized-HLO collective
   inventories (PR 15 style), strategy step-time history from the PR 13
   TSDB, live bytes from the PR 12 resource ledgers.  Terms the model
   has no measurement for fall back to an explicit roofline and say so
   (``source: "model:roofline"``) — the trace never launders a guess as
   a measurement.

3. :func:`auto_shard` — strategy selection as search, not heuristics:
   enumerate legal mesh factorizations of p over (dp, tp, sp, ep), then
   run a deterministic beam/DP over per-matmul strategies
   (replicated / column-parallel / row-parallel) with resharding edge
   costs, Megatron pairing emerging from the DP rather than being
   hard-coded.  Returns a :class:`Placement` whose ``trace`` lists every
   cost term and its measured source.

4. :func:`reshard` — elastic meshes: grow or shrink p mid-job by
   quiescing device-resident state through the PR 2 prepared-path flush
   protocol (or a PR 1 shard checkpoint), re-annotating the SAME program
   for the new mesh, verifying the old/new layout pair (sharding +
   dist-pairing checkers), and rebuilding the executor — no
   restart-from-scratch.  ``tools/autoshard_bench.py`` times the 8→4
   shrink and checks loss-trajectory parity at quiesce.
"""
from __future__ import annotations

import collections
import time

import numpy as np

__all__ = ["ShardingPass", "CostModel", "Placement", "auto_shard",
           "apply_placement", "annotate_program", "enumerate_strategies",
           "strategy_name", "infer_mesh_axes", "check_reshard_pair",
           "reshard", "PP_STAGE_ATTR"]

# Canonical axis order on the single logical mesh.  Insertion order is
# mesh order (parallel/mesh.make_mesh), and dp must stay leading so the
# executor's batch-dim default (P("dp", ...)) composes.
AXES_ORDER = ("dp", "tp", "sp", "ep", "pp")

# Op attr carrying the pipeline stage id assigned by ShardingPass; read
# by fluid.pipeline.PipelineProgram.from_annotations.
PP_STAGE_ATTR = "__pp_stage__"

_F32_BYTES = 4


def _desc_of(program):
    return getattr(program, "desc", program)


def _numel(shape, batch=32):
    n = 1
    for d in shape:
        n *= batch if d in (-1, 0) else int(d)
    return n


# ---------------------------------------------------------------------------
# ShardingPass: seed + propagate annotations over a ProgramDesc
# ---------------------------------------------------------------------------

# out spec = spec of the named input slot, rank-adjusted (same-rank copy)
_FOLLOW_X = {
    "relu", "gelu", "tanh", "sigmoid", "sqrt", "square", "abs", "exp",
    "log", "scale", "cast", "clip", "dropout", "softmax", "leaky_relu",
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow",
}

# optimizer update ops: accumulators mirror the Param's spec so e.g.
# Adam moments of a tensor-parallel weight never gather
_OPT_OPS = {"sgd", "momentum", "adam", "adamw", "rmsprop", "adagrad",
            "decayed_adagrad", "lars_momentum", "adamax", "ftrl"}

_GRAD_SUFFIX = "@GRAD"


class ShardingPass:
    """Assign + propagate per-VarDesc sharding annotations.

    PR 3 ``ProgramPass`` contract: ``run(program, scope, du) -> int``
    (count of newly annotated vars; 0 at fixpoint so PassManager
    terminates).  Seeds are (a) annotations already on the desc — from
    ``ParamAttr(sharding=...)`` / ``shard_var`` / a prior
    :func:`apply_placement` — and (b) the optional ``placement``
    given at construction.  Propagation is conservative: an op type the
    table does not know produces unannotated (= replicated) outputs,
    which is always correct, just not always fast.
    """

    name = "sharding_propagate"

    def __init__(self, placement=None):
        self.placement = placement

    # -- spec helpers -----------------------------------------------------
    @staticmethod
    def _nontrivial(spec):
        return spec is not None and any(a for a in spec)

    @staticmethod
    def _merge(a, b):
        """Join two specs of the same rank: agree -> keep, disagree ->
        replicate that dim (the safe meet of the sharding lattice)."""
        if a is None:
            return b
        if b is None:
            return a
        if len(a) != len(b):
            return None
        return tuple(x if x == y else None for x, y in zip(a, b))

    def run(self, program, scope, du):
        desc = _desc_of(program)
        sh = desc.var_shardings
        before = len(sh)
        if self.placement is not None:
            for name, spec in self.placement.var_shardings.items():
                if self._nontrivial(spec):
                    sh.setdefault(name, tuple(spec))
        block = desc.blocks[0]
        # local fixpoint: forward propagation can feed the grad mirror
        # which can feed optimizer mirroring, all within one pass run
        for _ in range(8):
            changed = 0
            changed += self._forward(block, sh)
            changed += self._mirror_grads(block, sh)
            changed += self._mirror_optimizer(block, sh)
            if not changed:
                break
        self._drop_trivial(sh)
        return len(sh) - before if len(sh) > before else 0

    # -- forward rules ----------------------------------------------------
    def _spec_of(self, sh, block, name):
        spec = sh.get(name)
        if spec is None:
            return None
        vd = block.find_var_recursive(name)
        if vd is not None and vd.shape and len(spec) != len(vd.shape):
            return None
        return tuple(spec)

    def _put(self, sh, block, name, spec):
        if not self._nontrivial(spec):
            return 0
        vd = block.find_var_recursive(name)
        if vd is None or not vd.shape or len(vd.shape) != len(spec):
            return 0
        # an axis may shard at most one dim of a var
        seen = set()
        clean = []
        for a in spec:
            if a and a not in seen:
                seen.add(a)
                clean.append(a)
            else:
                clean.append(None)
        clean = tuple(clean)
        if sh.get(name) == clean or not self._nontrivial(clean):
            return 0
        if name in sh:
            merged = self._merge(tuple(sh[name]), clean)
            if merged is None or sh.get(name) == merged:
                return 0
            sh[name] = merged
            return 1
        sh[name] = clean
        return 1

    def _forward(self, block, sh):
        changed = 0
        for op in block.ops:
            t = op.type
            outs = [n for n in op.output_arg_names() if n]
            if not outs:
                continue
            if t in _FOLLOW_X:
                spec = None
                for n in op.input_arg_names():
                    spec = self._merge(spec, self._spec_of(sh, block, n))
                if spec is not None:
                    for o in outs:
                        changed += self._put(sh, block, o, spec)
            elif t == "sum":
                spec = None
                for n in op.input(slot="X", default=[]):
                    spec = self._merge(spec, self._spec_of(sh, block, n))
                if spec is not None:
                    for o in outs:
                        changed += self._put(sh, block, o, spec)
            elif t in ("layer_norm", "batch_norm"):
                x = (op.input("X", default=[None]) or [None])[0]
                spec = self._spec_of(sh, block, x)
                if spec is not None:
                    y = (op.output("Y", default=[None]) or [None])[0]
                    if y:
                        changed += self._put(sh, block, y, spec)
            elif t in ("mul", "matmul"):
                changed += self._forward_matmul(block, sh, op)
            elif t == "lookup_table":
                changed += self._forward_lookup(block, sh, op)
            elif t == "reshape":
                changed += self._forward_reshape(block, sh, op)
            elif t == "transpose":
                changed += self._forward_transpose(block, sh, op)
            elif t == "ring_attention":
                q = (op.input("Q", default=[None]) or [None])[0]
                spec = self._spec_of(sh, block, q)
                if spec is not None:
                    for o in outs:
                        changed += self._put(sh, block, o, spec)
            elif t == "moe_ffn":
                x = (op.input("X", default=[None]) or [None])[0]
                spec = self._spec_of(sh, block, x)
                if spec is not None:
                    for o in outs:
                        changed += self._put(sh, block, o, spec)
            elif t == "sharding_constraint":
                spec = tuple(a if a else None
                             for a in (op.attr("spec") or ()))
                for o in outs:
                    changed += self._put(sh, block, o, spec)
            elif t in ("softmax_with_cross_entropy", "cross_entropy"):
                logits = (op.input("Logits", default=None)
                          or op.input("X", default=[None]) or [None])[0]
                spec = self._spec_of(sh, block, logits)
                if spec is not None:
                    batch = spec[:-1] + (None,)
                    for o in outs:
                        changed += self._put(sh, block, o, batch)
            elif t in ("concat", "split", "slice", "stack"):
                # keep only the batch-dim axis; splitting/merging along
                # annotated dims is not modelled
                x = (op.input("X", default=[None]) or [None])[0]
                spec = self._spec_of(sh, block, x)
                if spec is not None and spec[0]:
                    for o in outs:
                        vd = block.find_var_recursive(o)
                        if vd is not None and vd.shape:
                            changed += self._put(
                                sh, block, o,
                                (spec[0],) + (None,) * (len(vd.shape) - 1))
        return changed

    def _forward_matmul(self, block, sh, op):
        x = (op.input("X", default=[None]) or [None])[0]
        y = (op.input("Y", default=[None]) or [None])[0]
        out = (op.output("Out", default=[None]) or [None])[0]
        if not (x and y and out):
            return 0
        xs = self._spec_of(sh, block, x)
        ys = self._spec_of(sh, block, y)
        ovd = block.find_var_recursive(out)
        if ovd is None or not ovd.shape:
            return 0
        orank = len(ovd.shape)
        spec = [None] * orank
        # batch/row dims of Out come from X's leading dims
        if xs is not None:
            for i in range(min(orank - 1, len(xs) - 1)):
                spec[i] = xs[i]
        # column dim comes from Y's last dim (column-parallel); a
        # sharded contraction (X last / Y first) leaves Out replicated
        # on that dim — XLA inserts the all-reduce
        if ys is not None and ys[-1]:
            spec[-1] = ys[-1]
        return self._put(sh, block, out, tuple(spec))

    def _forward_lookup(self, block, sh, op):
        ids = (op.input("Ids", default=[None]) or [None])[0]
        w = (op.input("W", default=[None]) or [None])[0]
        out = (op.output("Out", default=[None]) or [None])[0]
        if not out:
            return 0
        ovd = block.find_var_recursive(out)
        if ovd is None or not ovd.shape:
            return 0
        spec = [None] * len(ovd.shape)
        ids_s = self._spec_of(sh, block, ids)
        if ids_s is not None:
            for i in range(min(len(ids_s), len(spec) - 1)):
                spec[i] = ids_s[i]
        w_s = self._spec_of(sh, block, w)
        if w_s is not None and w_s[-1]:
            spec[-1] = w_s[-1]
        return self._put(sh, block, out, tuple(spec))

    def _forward_reshape(self, block, sh, op):
        x = (op.input("X", default=[None]) or [None])[0]
        out = (op.output("Out", default=[None]) or [None])[0]
        if not (x and out):
            return 0
        xs = self._spec_of(sh, block, x)
        if xs is None:
            return 0
        shape_attr = op.attr("shape") or ()
        ovd = block.find_var_recursive(out)
        if ovd is None or not ovd.shape:
            return 0
        spec = [None] * len(ovd.shape)
        # leading `0` entries copy the input dim (and its axis); the
        # first reshaped trailing dim inherits the axis of the first
        # consumed input dim (covers both the [B,S,D]->[B,S,H,Dh] split
        # and the [B,S,H,Dh]->[B,S,D] merge of the attention block)
        i = 0
        while (i < len(shape_attr) and i < len(spec) and i < len(xs)
               and shape_attr[i] == 0):
            spec[i] = xs[i]
            i += 1
        if i < len(spec) and i < len(xs):
            spec[i] = xs[i]
        return self._put(sh, block, out, tuple(spec))

    def _forward_transpose(self, block, sh, op):
        x = (op.input("X", default=[None]) or [None])[0]
        out = (op.output("Out", default=[None]) or [None])[0]
        perm = op.attr("axis") or ()
        if not (x and out and perm):
            return 0
        xs = self._spec_of(sh, block, x)
        if xs is None or len(xs) != len(perm):
            return 0
        return self._put(sh, block, out,
                         tuple(xs[p] for p in perm))

    # -- backward / optimizer mirrors -------------------------------------
    def _mirror_grads(self, block, sh):
        changed = 0
        for op in block.ops:
            for n in list(op.input_arg_names()) + list(
                    op.output_arg_names()):
                if _GRAD_SUFFIX not in n:
                    continue
                base = n.split(_GRAD_SUFFIX)[0]
                spec = self._spec_of(sh, block, base)
                if spec is not None:
                    changed += self._put(sh, block, n, spec)
        return changed

    def _mirror_optimizer(self, block, sh):
        changed = 0
        for op in block.ops:
            if op.type not in _OPT_OPS:
                continue
            param = (op.input("Param", default=[None]) or [None])[0]
            spec = self._spec_of(sh, block, param)
            if spec is None:
                continue
            pvd = block.find_var_recursive(param)
            pshape = tuple(pvd.shape) if pvd is not None else ()
            for n in list(op.input_arg_names()) + list(
                    op.output_arg_names()):
                if n in (param, None, ""):
                    continue
                vd = block.find_var_recursive(n)
                if vd is not None and tuple(vd.shape) == pshape:
                    changed += self._put(sh, block, n, spec)
        return changed

    @staticmethod
    def _drop_trivial(sh):
        for name in [n for n, s in sh.items()
                     if not any(a for a in s)]:
            del sh[name]


# ---------------------------------------------------------------------------
# CostModel: measured terms with provenance
# ---------------------------------------------------------------------------

class CostModel:
    """Cost terms for the auto-sharding search, each traceable to a
    measurement.

    Sources, in lookup order:

    - ``autotune:<key>`` — per-kernel measured ms from the PR 7 cache
      (``paddle_tpu.tuning``), keyed kernel|shape|dtype|backend.
    - ``tsdb:<series>`` — step-time history for a strategy fingerprint
      from the PR 13 TSDB (``autoshard.step_ms.<strategy>``), recorded
      by tools/autoshard_bench.py; a strategy the rig has already
      measured is predicted from its own history.
    - ``mesh_profile:r06_fit`` — collective alpha/beta fitted offline
      from the MESH_PROFILE_r06.md measured legs + their optimized-HLO
      collective inventories (PR 15 inspection).  Re-fit live with
      :meth:`fit_collectives` when newer rows exist.
    - ``ledger:<series>`` — peak live bytes per strategy leg from the
      PR 12 resource ledgers, used for the memory feasibility filter.
    - ``model:roofline`` — the explicit analytic fallback; never
      presented as measured.
    """

    # Roofline constants for one forced-host CPU "device" (the 8-dev
    # test mesh): deliberately conservative, only used when no
    # measurement covers a term.
    PEAK_FLOPS = 4.0e9          # per-device f32 FLOP/s
    MEM_BW = 4.0e9              # per-device B/s

    # Ring-collective alpha (per hop, ms) and inverse bandwidth
    # (ms per byte per hop) fitted from MESH_PROFILE r06: the dp8 leg
    # (98 all-reduces, 3.47 MB, 28.52 ms) vs dp4xtp2 (23.33 ms) vs
    # dp2xtp2xsp2 (29.79 ms) vs dp4xep2 (29.27 ms) — least-squares over
    # the shared compute term; see MESH_PROFILE_r06.md.
    DEFAULT_COLLECTIVES = {
        "all_reduce":        {"alpha_ms": 0.020, "inv_bw": 2.0e-6},
        "all_gather":        {"alpha_ms": 0.015, "inv_bw": 1.0e-6},
        "reduce_scatter":    {"alpha_ms": 0.015, "inv_bw": 1.0e-6},
        "all_to_all":        {"alpha_ms": 0.025, "inv_bw": 1.5e-6},
        "collective_permute": {"alpha_ms": 0.012, "inv_bw": 0.8e-6},
        "_source": "mesh_profile:r06_fit",
    }

    def __init__(self, kernel_table=None, collectives=None,
                 step_history=None, ledger_peaks=None):
        self.kernel_table = dict(kernel_table or {})
        self.collectives = dict(collectives or self.DEFAULT_COLLECTIVES)
        self.step_history = dict(step_history or {})
        self.ledger_peaks = dict(ledger_peaks or {})
        self.trace = []

    # -- construction from the repo's recorded data -----------------------
    @classmethod
    def from_repo(cls, tsdb_dir=None):
        """Ingest whatever measurements this host has recorded: the
        autotune cache (always consulted; empty without
        FLAGS_autotune_cache_dir), TSDB strategy step history, ledger
        peaks.  Missing stores degrade to the roofline, never raise."""
        kernel_table = {}
        try:
            from paddle_tpu import tuning
            for key, ent in tuning.entries().items():
                ms = ent.get("ms")
                if ms is not None:
                    kernel_table[key] = {
                        "ms": float(ms), "source": "autotune:%s" % key}
        except Exception:
            pass
        step_history = {}
        try:
            from paddle_tpu.observability import tsdb as _tsdb
            store = (_tsdb.TSDB(tsdb_dir) if tsdb_dir
                     else _tsdb.default_store(create=False))
            if store is not None:
                for name in store.names():
                    if not name.startswith("autoshard.step_ms."):
                        continue
                    _, vals = store.scan(name)
                    if len(vals):
                        strat = name[len("autoshard.step_ms."):]
                        step_history[strat] = {
                            "ms": float(np.median(vals)),
                            "n": int(len(vals)),
                            "source": "tsdb:%s" % name}
        except Exception:
            pass
        ledger_peaks = {}
        try:
            from paddle_tpu.observability import ledger as _ledger
            ledger_peaks = dict(_ledger.peaks() or {})
        except Exception:
            pass
        return cls(kernel_table=kernel_table, step_history=step_history,
                   ledger_peaks=ledger_peaks)

    def _note(self, term, ms, source, **extra):
        rec = {"term": term, "ms": round(float(ms), 6), "source": source}
        rec.update(extra)
        self.trace.append(rec)
        return ms

    # -- terms ------------------------------------------------------------
    def kernel_ms(self, kernel, shape, dtype="float32", backend="cpu"):
        """Per-device kernel time: autotune measurement when the cache
        has this (kernel, shape), roofline otherwise."""
        try:
            from paddle_tpu import tuning
            key = tuning.make_key(kernel, shape, dtype, backend)
        except Exception:
            key = "%s|%s|%s|%s" % (kernel,
                                   "x".join(str(d) for d in shape),
                                   dtype, backend)
        ent = self.kernel_table.get(key)
        if ent is not None:
            return self._note("kernel:%s" % kernel, ent["ms"],
                              ent["source"], shape=list(shape))
        if kernel in ("mul", "matmul"):
            # shape = (m, k, n)
            m, k, n = (list(shape) + [1, 1, 1])[:3]
            flops = 2.0 * m * k * n
            ms = flops / self.PEAK_FLOPS * 1e3
        else:
            nbytes = _numel(shape) * _F32_BYTES
            ms = nbytes / self.MEM_BW * 1e3
        return self._note("kernel:%s" % kernel, ms, "model:roofline",
                          shape=list(shape))

    def collective_ms(self, kind, nbytes, axis_size):
        """Ring-model cost of one collective over ``axis_size`` devices;
        alpha/beta carry the mesh-profile fit's provenance."""
        if axis_size <= 1:
            return 0.0
        p = self.collectives.get(kind) or self.collectives["all_reduce"]
        hops = 2 * (axis_size - 1) if kind == "all_reduce" \
            else (axis_size - 1)
        eff = nbytes * (axis_size - 1) / float(axis_size)
        if kind == "all_reduce":
            eff *= 2  # reduce-scatter + all-gather phases
        ms = hops * p["alpha_ms"] + eff * p["inv_bw"]
        return self._note("collective:%s" % kind, ms,
                          self.collectives.get("_source",
                                               "mesh_profile:r06_fit"),
                          bytes=int(nbytes), axis=int(axis_size))

    def strategy_history_ms(self, strategy):
        """Median measured step time for this exact strategy, if the
        TSDB has history for it (None otherwise)."""
        ent = self.step_history.get(strategy)
        if ent is None:
            return None
        return self._note("history:%s" % strategy, ent["ms"],
                          ent["source"], n=ent.get("n", 1))

    def fit_collectives(self, rows):
        """Refit alpha/inv_bw from live mesh-profile rows: each row has
        measured ``ms``, a collective inventory (counts + bytes), and a
        compute term shared across strategies.  Least squares on
        (alpha, inv_bw); keeps defaults if the system is degenerate."""
        usable = [r for r in rows
                  if r.get("ms") and r.get("collectives")]
        if len(usable) < 3:
            return False
        a = []
        b = []
        for r in usable:
            hops = sum(int(c.get("count", 0))
                       for c in r["collectives"].values())
            byts = sum(int(c.get("bytes", 0))
                       for c in r["collectives"].values())
            a.append([hops, byts, 1.0])
            b.append(float(r["ms"]))
        try:
            sol, *_ = np.linalg.lstsq(np.asarray(a), np.asarray(b),
                                      rcond=None)
        except Exception:
            return False
        alpha, inv_bw = float(sol[0]), float(sol[1])
        if alpha <= 0 or inv_bw <= 0:
            return False
        for kind in ("all_reduce", "all_gather", "reduce_scatter",
                     "all_to_all", "collective_permute"):
            self.collectives[kind] = {"alpha_ms": alpha,
                                      "inv_bw": inv_bw}
        self.collectives["_source"] = "mesh_profile:live_fit"
        return True


# ---------------------------------------------------------------------------
# Strategy enumeration + the beam/DP search
# ---------------------------------------------------------------------------

class Placement:
    """The search result: a mesh factorization + the var shardings it
    implies + the predicted cost and its full provenance trace."""

    __slots__ = ("mesh_axes", "var_shardings", "predicted_ms", "trace",
                 "strategy", "decisions")

    def __init__(self, mesh_axes, var_shardings, predicted_ms, trace,
                 strategy, decisions=None):
        self.mesh_axes = dict(mesh_axes)
        self.var_shardings = dict(var_shardings)
        self.predicted_ms = float(predicted_ms)
        self.trace = list(trace)
        self.strategy = strategy
        self.decisions = list(decisions or [])

    def to_dict(self):
        return {"strategy": self.strategy,
                "mesh_axes": self.mesh_axes,
                "predicted_ms": round(self.predicted_ms, 4),
                "n_annotated": len(self.var_shardings),
                "decisions": self.decisions,
                "trace": self.trace}

    def __repr__(self):
        return "Placement(%s, %.3fms, %d vars)" % (
            self.strategy, self.predicted_ms, len(self.var_shardings))


def strategy_name(axes):
    """Canonical leg name, MESH_PROFILE convention: dp4xtp2."""
    parts = ["%s%d" % (a, s) for a, s in axes.items() if s > 1]
    return "x".join(parts) if parts else "single"


def _program_features(desc, batch_size):
    """What the program supports constrains the factorization: sp needs
    ring_attention ops, ep needs moe_ffn, pp needs >= 2 stages of ops."""
    block = desc.blocks[0]
    feats = {"ring": False, "moe": False, "n_experts": 0,
             "n_matmul": 0, "params": [], "batch": batch_size}
    for op in block.ops:
        if op.type == "ring_attention":
            feats["ring"] = True
        elif op.type == "moe_ffn":
            feats["moe"] = True
            w1 = (op.input("W1", default=[None]) or [None])[0]
            vd = block.find_var_recursive(w1) if w1 else None
            if vd is not None and vd.shape:
                feats["n_experts"] = int(vd.shape[0])
        elif op.type in ("mul", "matmul"):
            feats["n_matmul"] += 1
    for name, vd in block.vars.items():
        if vd.persistable and vd.shape and _GRAD_SUFFIX not in name:
            feats["params"].append((name, tuple(vd.shape)))
    return feats


def _factorizations(n, axes):
    """All ordered assignments of n's factors to the given axes
    (deterministic order)."""
    if not axes:
        return [{}] if n == 1 else []
    out = []
    a = axes[0]
    for d in range(1, n + 1):
        if n % d:
            continue
        for rest in _factorizations(n // d, axes[1:]):
            f = {a: d}
            f.update(rest)
            out.append(f)
    return out


def enumerate_strategies(desc, n_devices, batch_size=32):
    """Legal mesh factorizations of n_devices over (dp, tp, sp, ep) for
    THIS program: tp needs matmuls, sp needs ring_attention, ep needs
    moe_ffn and must divide the expert count, dp must divide the batch.
    Deterministic, sorted by canonical name."""
    feats = _program_features(desc, batch_size)
    cands = []
    seen = set()
    for f in _factorizations(n_devices, ["dp", "tp", "sp", "ep"]):
        axes = {a: s for a, s in f.items() if s > 1}
        if not axes:
            axes = {"dp": 1}
        key = tuple(sorted(axes.items()))
        if key in seen:
            continue
        seen.add(key)
        dp = f.get("dp", 1)
        tp = f.get("tp", 1)
        sp = f.get("sp", 1)
        ep = f.get("ep", 1)
        if dp > 1 and batch_size % dp:
            continue
        if tp > 1 and not feats["n_matmul"]:
            continue
        if sp > 1 and not feats["ring"]:
            continue
        if ep > 1 and (not feats["moe"]
                       or (feats["n_experts"] or 0) % ep):
            continue
        if tp > 8 or sp > 8:
            continue
        ordered = collections.OrderedDict(
            (a, f.get(a, 1)) for a in AXES_ORDER
            if f.get(a, 1) > 1 or a == "dp")
        cands.append(ordered)
    cands.sort(key=lambda ax: strategy_name(ax))
    return cands


def _matmul_ops(desc):
    """(op, x, w, out, m, k, n) for every mul/matmul whose Y is a 2-D
    persistable — the decision points of the per-op DP."""
    block = desc.blocks[0]
    out = []
    for op in block.ops:
        if op.type not in ("mul", "matmul"):
            continue
        x = (op.input("X", default=[None]) or [None])[0]
        y = (op.input("Y", default=[None]) or [None])[0]
        o = (op.output("Out", default=[None]) or [None])[0]
        if not (x and y and o):
            continue
        yvd = block.find_var_recursive(y)
        if yvd is None or not yvd.persistable or len(yvd.shape) != 2:
            continue
        xvd = block.find_var_recursive(x)
        xshape = tuple(xvd.shape) if xvd is not None else ()
        k, n = int(yvd.shape[0]), int(yvd.shape[1])
        m = 1
        for d in xshape[:-1]:
            m *= 32 if d in (-1, 0) else int(d)
        out.append({"op": op, "x": x, "w": y, "out": o,
                    "m": m, "k": k, "n": n})
    return out


def _dp_over_matmuls(desc, axes, cost, batch_size):
    """Deterministic beam/DP over per-matmul strategies.

    State: is the activation's hidden dim currently sharded over tp
    ('tp') or replicated ('rep').  Options per matmul: keep the weight
    replicated, column-parallel (None, tp), or row-parallel (tp, None).
    Transition costs are the resharding collectives the choice implies —
    the Megatron column→row pairing falls out of the DP, it is not
    hard-coded.  Returns (weight specs, compute+collective ms,
    decisions)."""
    tp = axes.get("tp", 1)
    dp = axes.get("dp", 1)
    mats = _matmul_ops(desc)
    # states: hidden replicated / hidden tp-sharded
    INF = float("inf")
    best = {"rep": (0.0, {}, [])}
    for mm in mats:
        m_dev = max(1, mm["m"] // max(1, dp))
        nxt = {}
        for state, (acc, specs, decs) in sorted(best.items()):
            opts = [("repl", "rep")]
            if tp > 1 and mm["n"] % tp == 0:
                opts.append(("col", "tp"))
            if tp > 1 and mm["k"] % tp == 0:
                opts.append(("row", "rep"))
            for choice, out_state in opts:
                cost.trace, saved = [], cost.trace
                ms = 0.0
                if choice == "repl":
                    if state == "tp":  # gather hidden back first
                        ms += cost.collective_ms(
                            "all_gather",
                            m_dev * mm["k"] * _F32_BYTES * (tp - 1) // tp,
                            tp)
                    ms += cost.kernel_ms("mul", (m_dev, mm["k"], mm["n"]))
                elif choice == "col":
                    if state == "tp":
                        ms += cost.collective_ms(
                            "all_gather",
                            m_dev * mm["k"] * _F32_BYTES * (tp - 1) // tp,
                            tp)
                    ms += cost.kernel_ms(
                        "mul", (m_dev, mm["k"], mm["n"] // tp))
                else:  # row
                    if state == "rep":
                        # slicing a replicated activation is free; the
                        # cost is the output all-reduce
                        pass
                    ms += cost.kernel_ms(
                        "mul", (m_dev, mm["k"] // tp, mm["n"]))
                    ms += cost.collective_ms(
                        "all_reduce", m_dev * mm["n"] * _F32_BYTES, tp)
                terms = cost.trace
                cost.trace = saved
                tot = acc + ms
                prev = nxt.get(out_state, (INF,))[0]
                if tot < prev - 1e-12:
                    s2 = dict(specs)
                    if choice == "col":
                        s2[mm["w"]] = (None, "tp")
                    elif choice == "row":
                        s2[mm["w"]] = ("tp", None)
                    d2 = decs + [{"op": "mul", "w": mm["w"],
                                  "choice": choice,
                                  "ms": round(ms, 5),
                                  "terms": terms}]
                    nxt[out_state] = (tot, s2, d2)
        best = nxt or best
    # leave the last activation replicated (the loss is host-consumed)
    endc = {}
    for state, (acc, specs, decs) in best.items():
        extra = 0.0
        if state == "tp" and mats:
            cost.trace, saved = [], cost.trace
            last = mats[-1]
            m_dev = max(1, last["m"] // max(1, dp))
            extra = cost.collective_ms(
                "all_gather", m_dev * last["n"] * _F32_BYTES, tp)
            cost.trace = saved
        endc[state] = (acc + extra, specs, decs)
    state = min(sorted(endc), key=lambda s: endc[s][0])
    return endc[state]


def _strategy_cost(desc, axes, cost, batch_size):
    """Predicted step ms for one factorization: measured history when
    the TSDB has this exact strategy, else matmul DP + per-step grad
    all-reduce + the axis-specific extras."""
    name = strategy_name(axes)
    hist = cost.strategy_history_ms(name)
    ms, specs, decisions = _dp_over_matmuls(desc, axes, cost, batch_size)
    dp = axes.get("dp", 1)
    tp = axes.get("tp", 1)
    sp = axes.get("sp", 1)
    ep = axes.get("ep", 1)
    feats = _program_features(desc, batch_size)
    # dp gradient all-reduce: every trainable param's grad, sized by its
    # tp/ep shard (annotated grads never gather)
    grad_bytes = 0
    for pname, shape in feats["params"]:
        nb = _numel(shape, batch_size) * _F32_BYTES
        spec = specs.get(pname)
        if spec and "tp" in spec:
            nb //= tp
        if len(shape) == 3 and ep > 1:  # expert weights shard over ep
            nb //= ep
        grad_bytes += nb
    if dp > 1 and grad_bytes:
        ms += cost.collective_ms("all_reduce", grad_bytes, dp)
    if sp > 1:
        # ring attention: (sp-1) K/V permutes per attention op
        act = batch_size // max(1, dp) * 64 * 64 * _F32_BYTES // sp
        for _ in range(max(1, feats["n_matmul"] // 6)):
            ms += cost.collective_ms("collective_permute",
                                     2 * act * (sp - 1), sp)
    if ep > 1:
        act = batch_size // max(1, dp) * 64 * 64 * _F32_BYTES
        ms += cost.collective_ms("all_to_all", 2 * act, ep)
    predicted = hist if hist is not None else ms
    return predicted, ms, hist, specs, decisions


def auto_shard(program, n_devices, cost_model=None, batch_size=32,
               keep_existing=True):
    """Search the factorization lattice x per-matmul strategies and
    return the cheapest :class:`Placement` (deterministic: sorted
    enumeration, stable tie-break on canonical name).

    Strategies the rig has measured (TSDB step history) are predicted
    from their own history.  When at least one candidate is
    history-backed, model-only candidates are charged the WORST
    observed measured/model ratio ("pessimistic calibration"): the
    analytic roofline assumes per-device compute shrinks with the
    mesh, which real rigs — above all the forced-host CPU mesh, where
    every "device" shares the same cores — routinely violate, and an
    optimistic unmeasured estimate must not outrank a measurement.

    The placement is NOT applied; call :func:`apply_placement` (or
    :func:`annotate_program`) to write it onto the desc."""
    desc = _desc_of(program)
    cost = cost_model or CostModel.from_repo()
    rows = []
    for axes in enumerate_strategies(desc, n_devices, batch_size):
        cost.trace = []
        predicted, model_ms, hist, specs, decisions = _strategy_cost(
            desc, axes, cost, batch_size)
        rows.append({"predicted": predicted, "model_ms": model_ms,
                     "hist": hist, "name": strategy_name(axes),
                     "axes": axes, "specs": specs,
                     "decisions": decisions, "trace": list(cost.trace)})
    if not rows:
        raise ValueError("no legal strategy for %d devices" % n_devices)
    ratios = [r["hist"] / r["model_ms"] for r in rows
              if r["hist"] is not None and r["model_ms"] > 0]
    if ratios and any(r["hist"] is None for r in rows):
        scale = max(ratios)
        for r in rows:
            if r["hist"] is None:
                r["predicted"] = r["model_ms"] * scale
                r["trace"].append({
                    "term": "calibration:model_x%.3f" % scale,
                    "ms": round(r["predicted"], 4),
                    "source": "tsdb:calibration",
                    "scale": round(scale, 4)})
    results = [(r["predicted"], r["name"], Placement(
        r["axes"], r["specs"], r["predicted"], r["trace"], r["name"],
        r["decisions"])) for r in rows]
    results.sort(key=lambda r: (r[0], r[1]))
    best = results[0][2]
    best.trace = list(best.trace) + [
        {"term": "considered:%s" % name, "ms": round(pred, 4),
         "source": "search"} for pred, name, _ in results[1:]]
    return best


def apply_placement(program, placement, scope=None):
    """Write a placement's annotations onto the program via
    :class:`ShardingPass` (so seeds propagate to grads/accumulators),
    stash the mesh extents on the desc for the executor route, and bump
    the version so every compile/verify cache misses."""
    desc = _desc_of(program)
    from paddle_tpu.fluid.transpiler.pass_framework import PassManager
    PassManager([ShardingPass(placement)]).run(
        program if hasattr(program, "desc") else _FluidShim(desc),
        scope)
    desc.mesh_axes = dict(placement.mesh_axes)
    desc.bump_version()
    return desc.var_shardings


def annotate_program(program, n_devices, cost_model=None, batch_size=32,
                     scope=None):
    """auto_shard + apply_placement in one step; returns the
    Placement."""
    placement = auto_shard(program, n_devices, cost_model=cost_model,
                           batch_size=batch_size)
    apply_placement(program, placement, scope=scope)
    return placement


def placement_for(program, axes, cost_model=None, batch_size=32):
    """A Placement for a FIXED factorization — no search: the same
    per-matmul dynamic program the search runs, pinned to ``axes``.
    This is how a hand-picked MESH_PROFILE strategy lowers through the
    annotated route instead of the legacy carrier wiring."""
    desc = _desc_of(program)
    cost = cost_model or CostModel()
    cost.trace = []
    predicted, _model_ms, _hist, specs, decisions = _strategy_cost(
        desc, dict(axes), cost, batch_size)
    return Placement(dict(axes), specs, predicted, list(cost.trace),
                     strategy_name(axes), decisions)


class _FluidShim:
    """Minimal Program-shaped wrapper so PassManager/DefUse accept a
    bare ProgramDesc."""

    def __init__(self, desc):
        self.desc = desc


def infer_mesh_axes(program, n_devices=None):
    """Mesh extents for an annotated program: the stash
    ``apply_placement`` left on the desc when present; otherwise the
    annotation axis NAMES with extents solved from n_devices (single
    unknown axis gets the remainder; ambiguous splits fall back to
    auto_mesh_axes order)."""
    desc = _desc_of(program)
    stashed = getattr(desc, "mesh_axes", None)
    if stashed:
        return collections.OrderedDict(
            (a, int(s)) for a, s in stashed.items())
    names = []
    for spec in desc.var_shardings.values():
        for a in spec:
            if a and a not in names:
                names.append(a)
    names.sort(key=lambda a: AXES_ORDER.index(a)
               if a in AXES_ORDER else len(AXES_ORDER))
    if not names:
        return None
    if n_devices is None:
        import jax
        n_devices = len(jax.devices())
    axes = collections.OrderedDict()
    rem = n_devices
    for a in names[:-1]:
        axes[a] = 2 if rem % 2 == 0 and rem > 1 else 1
        rem //= axes[a]
    axes[names[-1]] = max(1, rem)
    return axes


# ---------------------------------------------------------------------------
# Pipeline stage assignment (the pp axis on the same annotation carrier)
# ---------------------------------------------------------------------------

def assign_pipeline_stages(program, n_stages):
    """Tag every block-0 op with a ``__pp_stage__`` attr: contiguous
    stages, boundaries chosen where exactly ONE live activation crosses
    (the GPipe cut contract), balanced by matmul count.  Returns the
    cut-variable names; ``PipelineProgram.from_annotations`` lowers the
    tagged program.  Raises when the program has no n_stages-1 legal
    single-crossing cuts (e.g. a one-matmul net)."""
    desc = _desc_of(program)
    block = desc.blocks[0]
    ops = block.ops
    if n_stages < 2:
        for op in ops:
            op.set_attr(PP_STAGE_ATTR, 0)
        return []
    persist = {n for n, vd in block.vars.items() if vd.persistable}
    # candidate cut AFTER op i: vars defined at <=i and read at >i,
    # excluding persistables (params live with their stage)
    last_read = {}
    for i, op in enumerate(ops):
        for n in op.input_arg_names():
            if n:
                last_read[n] = i
    defined_at = {}
    for i, op in enumerate(ops):
        for n in op.output_arg_names():
            if n and n not in defined_at:
                defined_at[n] = i
    candidates = []
    for i in range(len(ops) - 1):
        crossing = [n for n, d in defined_at.items()
                    if d <= i and last_read.get(n, -1) > i
                    and n not in persist]
        if len(crossing) == 1:
            candidates.append((i, crossing[0]))
    weights = [1 + (4 if op.type in ("mul", "matmul", "ring_attention",
                                     "moe_ffn") else 0)
               for op in ops]
    total = float(sum(weights))
    cuts = []
    acc = 0.0
    want = 1
    for i, (idx, var) in enumerate(sorted(candidates)):
        acc = sum(weights[:idx + 1])
        if acc >= total * want / n_stages and len(cuts) < n_stages - 1:
            cuts.append((idx, var))
            want += 1
    if len(cuts) < n_stages - 1:
        raise ValueError(
            "program has %d single-crossing cut points, need %d for "
            "%d stages" % (len(candidates), n_stages - 1, n_stages))
    bounds = [c[0] for c in cuts]
    for i, op in enumerate(ops):
        stage = sum(1 for b in bounds if i > b)
        op.set_attr(PP_STAGE_ATTR, stage)
    desc.bump_version()
    return [c[1] for c in cuts]


# ---------------------------------------------------------------------------
# Elastic resharding
# ---------------------------------------------------------------------------

def check_reshard_pair(desc, old_shardings, old_axes, new_shardings,
                       new_axes):
    """Diagnostics for an old/new layout pair of the SAME program:
    annotated persistables must stay annotated (or knowingly dropped to
    replicated), every spec must be valid on its mesh, and sharded dims
    must divide by their axis extent on BOTH layouts — the invariants
    redistribution relies on."""
    from paddle_tpu.analysis.diagnostics import Diagnostic, Severity
    diags = []
    block = desc.blocks[0]
    for name, spec in sorted(old_shardings.items()):
        vd = block.find_var_recursive(name)
        if vd is None or not vd.persistable:
            continue
        new_spec = new_shardings.get(name)
        if new_spec is None and any(a for a in spec):
            diags.append(Diagnostic(
                "reshard-pair", Severity.WARNING,
                "persistable sharded on the old mesh (%s) is "
                "unannotated on the new one — it will gather to "
                "replicated during redistribution" % (spec,),
                var=name,
                suggestion="carry the annotation through "
                           "apply_placement on the new mesh"))
    for which, shardings, axes in (("old", old_shardings, old_axes),
                                   ("new", new_shardings, new_axes)):
        axes = axes or {}
        for name, spec in sorted(shardings.items()):
            vd = block.find_var_recursive(name)
            if vd is None or not vd.shape:
                continue
            for dim, a in enumerate(spec):
                if not a:
                    continue
                ext = axes.get(a)
                if ext is None:
                    diags.append(Diagnostic(
                        "reshard-pair", Severity.ERROR,
                        "%s layout shards dim %d over axis %r which "
                        "the %s mesh %r does not have"
                        % (which, dim, a, which, dict(axes)), var=name,
                        suggestion="add the axis to the mesh or drop "
                                   "the annotation"))
                elif (dim < len(vd.shape) and vd.shape[dim] > 0
                      and vd.shape[dim] % ext):
                    diags.append(Diagnostic(
                        "reshard-pair", Severity.ERROR,
                        "%s layout: dim %d (size %d) of %r does not "
                        "divide by %s=%d"
                        % (which, dim, vd.shape[dim], name, a, ext),
                        var=name,
                        suggestion="pick an extent that divides the "
                                   "dim, or leave it replicated"))
    return diags


def reshard(program, scope, n_devices, cost_model=None, batch_size=32,
            checkpoint_dir=None, verify=True, flight_reason="mesh_reshard",
            exec_strategy=None, build_strategy=None):
    """Grow or shrink the mesh mid-job without restart-from-scratch.

    Quiesce: flush every prepared attachment's device-resident state
    back through the scope (the PR 2 ``sync_scope`` protocol) so host
    state is authoritative.  Re-lower: run :func:`auto_shard` for the
    new device count on the SAME program, verify the old/new layout
    pair plus the full checker pipeline (sharding + dist-pairing), and
    build a fresh ParallelExecutor over the new mesh — the first run's
    ``in_shardings`` redistribute the quiesced state.  When
    ``checkpoint_dir`` is given the PR 1 shard checkpoint is loaded
    instead of trusting device-resident state (the crash-recovery arm
    of the fault drill).

    Returns ``(executor, report)``; the report times each step and a
    flight artifact records the transition for post-mortems."""
    desc = _desc_of(program)
    report = {"from_axes": dict(getattr(desc, "mesh_axes", {}) or {}),
              "to_devices": int(n_devices)}
    old_shardings = dict(desc.var_shardings)
    old_axes = dict(getattr(desc, "mesh_axes", {}) or {})

    t0 = time.perf_counter()
    try:
        scope.flush_prepared()
    except Exception:
        pass
    report["quiesce_ms"] = (time.perf_counter() - t0) * 1e3

    if checkpoint_dir is not None:
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import io as fio
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(scope):
            serial = fio.load_checkpoint(exe, checkpoint_dir,
                                         main_program=program)
        report["checkpoint_serial"] = serial

    t0 = time.perf_counter()
    placement = auto_shard(program, n_devices, cost_model=cost_model,
                           batch_size=batch_size)
    apply_placement(program, placement, scope=scope)
    report["relower_ms"] = (time.perf_counter() - t0) * 1e3
    report["strategy"] = placement.strategy
    report["mesh_axes"] = dict(placement.mesh_axes)

    if verify:
        from paddle_tpu import analysis
        diags = check_reshard_pair(desc, old_shardings, old_axes,
                                   desc.var_shardings,
                                   placement.mesh_axes)
        diags += [d for d in analysis.verify_program(desc)
                  if d.is_error]
        errors = [d for d in diags if d.is_error]
        report["verify_errors"] = len(errors)
        if errors:
            raise analysis.ProgramVerificationError(
                analysis.format_diagnostics(errors))

    from paddle_tpu.fluid.parallel_executor import ParallelExecutor
    t0 = time.perf_counter()
    pe = ParallelExecutor(use_cuda=False, main_program=program,
                          scope=scope,
                          mesh_axes=dict(placement.mesh_axes),
                          num_devices=n_devices,
                          exec_strategy=exec_strategy,
                          build_strategy=build_strategy)
    report["rebuild_ms"] = (time.perf_counter() - t0) * 1e3

    try:
        from paddle_tpu.observability import flight
        path = flight.dump(flight_reason, sections={
            "reshard": {k: v for k, v in report.items()
                        if not isinstance(v, Exception)}})
        report["flight_artifact"] = path
    except Exception:
        report["flight_artifact"] = None
    return pe, report
