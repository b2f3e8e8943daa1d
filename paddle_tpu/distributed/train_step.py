"""ShardedTrainStep: ONE jitted SPMD program = forward + backward + update.

Capability parity: this replaces the reference's entire multi-device
execution stack — ParallelExecutor SSA graphs (`parallel_executor.cc:443`,
`details/all_reduce_op_handle.cc`), the collective transpiler
(`transpiler/collective.py:178` inserting c_allreduce_sum per grad) and the
parameter-server topology (`distribute_transpiler.py:545`).  Under GSPMD
there is no graph rewriting: batch is sharded on `dp`, params on `tp` (and
optionally `sp` for sequence), optimizer state ZeRO-sharded on `dp`; XLA
inserts the all-reduces/all-gathers the reference spelled as c_* ops.

The model is any dygraph Layer; its forward traces through the tape (pure
JAX), grads come from `jax.grad` over the functional application, and the
update math reuses the registered optimizer-op lowerings — so the numerics
are byte-identical to the single-device fluid path.

`zero_stage=2|3` (ZeRO, Rajbhandari et al. 2020) switches the dp axis
from GSPMD's implicit all-reduce to EXPLICIT communication: bucketed
`psum_scatter` gradient sync, the optimizer update on each rank's 1/N
shard (`distributed/zero.py` layouts), and per-bucket all-gathers XLA
can overlap — plus `accumulate_steps=k` microbatch accumulation that
communicates gradients once per outer step.  `collective_stats()`
extracts the compiled HLO's actual collectives so tests (and
`bench.py --multichip`) can assert reduce-scatter replaced all-reduce.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from ..fluid import framework
from ..fluid.core.registry import LowerContext, get_op_def
from ..observability import trace as _trace
from ..observability.metrics import default_registry
from ..observability.xla_cost import feed_signature
from .sharding import ShardingRule, megatron_rule, replicated_rule, zero_shard_state
from .topology import DeviceMesh

# optimizer-op adapter table: op_type -> (state slots, per-state init)
_STATE_SLOTS = {
    "sgd": [],
    "momentum": [("Velocity", "zeros_like")],
    "adam": [
        ("Moment1", "zeros_like"),
        ("Moment2", "zeros_like"),
        ("Beta1Pow", "beta1"),
        ("Beta2Pow", "beta2"),
    ],
}
_STATE_SLOTS["adamw"] = _STATE_SLOTS["adam"]
_STATE_SLOTS["lamb"] = _STATE_SLOTS["adam"]
_OUT_SLOT = {
    "Velocity": "VelocityOut",
    "Moment1": "Moment1Out",
    "Moment2": "Moment2Out",
    "Beta1Pow": "Beta1PowOut",
    "Beta2Pow": "Beta2PowOut",
}


class FunctionalOptimizer:
    """Pure-pytree adapter over a fluid Optimizer's update op."""

    def __init__(self, fluid_opt):
        from ..fluid import optimizer as opt_mod

        self._opt = fluid_opt
        self.attrs = {}
        if isinstance(fluid_opt, opt_mod.SGDOptimizer):
            self.op_type = "sgd"
        elif isinstance(fluid_opt, opt_mod.LambOptimizer):
            self.op_type = "lamb"
            self.attrs = {
                "beta1": fluid_opt._beta1, "beta2": fluid_opt._beta2,
                "epsilon": fluid_opt._epsilon,
                "weight_decay": fluid_opt._weight_decay,
            }
        elif isinstance(fluid_opt, opt_mod.AdamWOptimizer):
            self.op_type = "adamw"
            self.attrs = {
                "beta1": fluid_opt._beta1, "beta2": fluid_opt._beta2,
                "epsilon": fluid_opt._epsilon, "coeff": fluid_opt._coeff,
            }
        elif isinstance(fluid_opt, opt_mod.AdamOptimizer):
            self.op_type = "adam"
            self.attrs = {
                "beta1": fluid_opt._beta1, "beta2": fluid_opt._beta2,
                "epsilon": fluid_opt._epsilon,
            }
        elif isinstance(fluid_opt, opt_mod.MomentumOptimizer):
            self.op_type = "momentum"
            self.attrs = {
                "mu": fluid_opt._momentum,
                "use_nesterov": fluid_opt._use_nesterov,
            }
        else:
            raise NotImplementedError(
                "FunctionalOptimizer: no adapter for %s (add a state-slot "
                "table entry)" % type(fluid_opt).__name__
            )
        self._opdef = get_op_def(self.op_type)

    @property
    def learning_rate(self):
        lr = self._opt._learning_rate
        return float(lr) if not callable(lr) else lr

    def state_shapes(self, params):
        out = {}
        for name, p in params.items():
            out[name] = {}
            for slot, _init in _STATE_SLOTS[self.op_type]:
                shape = (1,) if slot.endswith("Pow") else tuple(p.shape)
                out[name][slot] = shape
        return out

    def init_state(self, params):
        state = {}
        for name, p in params.items():
            st = {}
            for slot, init in _STATE_SLOTS[self.op_type]:
                if init == "zeros_like":
                    st[slot] = jnp.zeros(p.shape, jnp.float32)
                elif init == "beta1":
                    st[slot] = jnp.full((1,), self.attrs.get("beta1", 0.9), jnp.float32)
                elif init == "beta2":
                    st[slot] = jnp.full((1,), self.attrs.get("beta2", 0.999), jnp.float32)
            state[name] = st
        return state

    def apply(self, params, grads, state, lr):
        """(params, grads, state, scalar lr) -> (new_params, new_state).
        Traced under the scope ``optimizer_update``, by which a device
        trace finds the update's operations."""
        ctx = LowerContext(base_key=None, is_test=False)
        new_params, new_state = {}, {}
        with jax.named_scope("optimizer_update"):
            for name, p in params.items():
                g = grads[name]
                ins = {
                    "Param": [p],
                    "Grad": [g],
                    "LearningRate": [jnp.asarray(lr, jnp.float32)],
                }
                for slot, _ in _STATE_SLOTS[self.op_type]:
                    ins[slot] = [state[name][slot]]
                outs = self._opdef.lower(ctx, ins, self.attrs)
                new_params[name] = outs["ParamOut"][0]
                new_state[name] = {
                    slot: outs[_OUT_SLOT[slot]][0]
                    for slot, _ in _STATE_SLOTS[self.op_type]
                }
        return new_params, new_state

    @property
    def pow_slots(self):
        """State slots holding beta-power scalars (replicated under
        ZeRO: shape (1,) cannot shard, and their update needs no
        gradient)."""
        return [slot for slot, _ in _STATE_SLOTS[self.op_type]
                if slot.endswith("Pow")]

    @property
    def moment_slots(self):
        """Per-element state slots shaped like the param (the ones ZeRO
        shards alongside it)."""
        return [slot for slot, _ in _STATE_SLOTS[self.op_type]
                if not slot.endswith("Pow")]

    def advance_pow(self, slot, value):
        """One step of a beta-power slot's recurrence: ``pow *= beta``.

        This IS the op lowering's contract (`_adam`/`_lamb` compute
        ``Beta1PowOut = Beta1Pow * beta1``), restated here so the
        ZeRO-2/3 step can advance the replicated pow scalars OUTSIDE
        the per-rank sharded update — the in-body PowOut would need a
        collective purely to re-prove replication.  Guarded by the
        oracle-parity drills: if the lowering's recurrence ever drifts,
        the stage-2-vs-GSPMD state comparison fails."""
        beta = self.attrs.get(
            "beta1" if slot.startswith("Beta1") else "beta2",
            0.9 if slot.startswith("Beta1") else 0.999)
        return value * beta


class ShardedTrainStep:
    """Compile a dygraph Layer + fluid optimizer into one SPMD step.

    loss_fn(model, batch_dict) -> scalar loss VarBase, written in normal
    dygraph style.  batch_specs: {key: PartitionSpec-like tuple}; defaults
    shard dim 0 on dp (and dim 1 on sp when the mesh has sp > 1).

    ``zero_stage`` (Rajbhandari et al., 2020):

    * 0/1 — ONE GSPMD jit; XLA inserts the gradient all-reduce from
      sharding propagation; stage 1 shards optimizer moments on dp.
    * 2   — explicit comm: gradients are reduce-scattered over dp
      (bucketed, one ``psum_scatter`` per <= ``gather_chunk_bytes``
      chunk), the optimizer update runs on each rank's 1/N shard, and
      the updated params re-replicate through per-bucket all-gathers
      XLA can overlap — the full-gradient all-reduce disappears from
      the compiled HLO (asserted by `collective_stats` consumers).
    * 3   — stage 2 + params stay SHARDED at rest; the step all-gathers
      them just-in-time at forward entry (per-bucket, overlap-ready)
      and the updated shards never re-replicate.

    Stages 2/3 run the dp axis in manual-collective mode
    (`jax.shard_map`) and therefore require a pure-dp mesh (tp/sp/ep
    composition stays on the GSPMD path for now).

    ``accumulate_steps=k`` splits the batch into k microbatches via a
    ``lax.scan`` that accumulates grads locally in f32 — at stage >= 2
    gradients are communicated exactly ONCE per outer step no matter
    the k.  Composes with ``remat``, ``amp="bf16"`` and donation.

    Loss-reduction convention: stage >= 2 (and any ``accumulate_steps``
    > 1) averages PER-SHARD / per-microbatch losses and gradients —
    exact when ``loss_fn`` is an unweighted mean over the batch.  A
    ratio-normalized loss (e.g. ``sum(w*l)/sum(w)`` MLM masking)
    becomes a mean of per-shard ratios, the standard DP/microbatch
    convention (DeepSpeed/Megatron likewise), which differs from the
    GSPMD path's single global ratio when per-shard weight sums are
    unequal; normalize inside ``loss_fn`` by a per-sample constant (or
    keep weight sums balanced across shards) when exact stage-1 parity
    matters.

    Usage::

        mesh = auto_mesh(tp=2)
        step = ShardedTrainStep(model, opt, loss_fn, mesh)
        state = step.init()              # shard + place params/opt state
        state, loss = step(state, batch) # one fused XLA program
    """

    def __init__(self, model, optimizer, loss_fn, mesh: DeviceMesh,
                 param_rule: ShardingRule = None, batch_specs=None,
                 zero_stage=1, donate=True, remat=False, amp=None,
                 prng_impl="rbg", accumulate_steps=1,
                 gather_chunk_bytes=None):
        if mesh.axis_size("pp") > 1:
            raise NotImplementedError(
                "pipeline stages use parallel.PipelineOptimizer (gpipe scan)"
            )
        if zero_stage not in (0, 1, 2, 3):
            raise ValueError("zero_stage must be 0..3, got %r" % (zero_stage,))
        if zero_stage >= 2:
            busy = [a for a in ("tp", "sp", "ep")
                    if mesh.axis_size(a) > 1]
            if busy:
                raise NotImplementedError(
                    "zero_stage>=2 shards gradients with manual dp "
                    "collectives and needs a pure-dp mesh; axes %s > 1 "
                    "(compose tp/sp via the GSPMD path, zero_stage<=1)"
                    % busy)
        self.model = model
        self.fopt = FunctionalOptimizer(optimizer)
        self.loss_fn = loss_fn
        self.mesh = mesh
        needs_rules = mesh.axis_size("tp") > 1 or mesh.axis_size("ep") > 1
        self.param_rule = param_rule or (
            megatron_rule() if needs_rules else replicated_rule()
        )
        self.batch_specs = batch_specs or {}
        self.zero_stage = zero_stage
        self.accumulate_steps = int(accumulate_steps)
        if self.accumulate_steps < 1:
            raise ValueError("accumulate_steps must be >= 1")
        from . import zero as zero_mod

        self.gather_chunk_bytes = int(
            gather_chunk_bytes if gather_chunk_bytes is not None
            else zero_mod.DEFAULT_CHUNK_BYTES)
        self._zero_layouts = None   # built by init() at stage >= 2
        self.remat = remat
        if amp not in (None, "bf16"):
            raise ValueError("amp must be None or 'bf16' (TPU needs no fp16 "
                             "loss scaling; cf. mixed_precision/decorator.py)")
        self.amp = amp
        # rbg = TPU hardware random-bit generator; threefry dropout masks
        # cost ~13 ms/step (28%) on BERT-base B=8,S=512 on one v5e chip.
        self.prng_impl = prng_impl
        # compiled step per batch signature: a batch whose shapes/dtypes
        # (and hence feed shardings) differ gets its own executable instead
        # of retracing against the first batch's stale in_shardings
        self._step_fns = {}
        self._hlo_texts = {}   # compiled_hlo memo (one AOT compile each)
        self._shardings = None
        self._m_dispatch_ms = default_registry().histogram(
            "train_step_dispatch_ms",
            "ShardedTrainStep call: batch placement and step dispatch, "
            "until the call returns (ms)")

    # -- state ----------------------------------------------------------
    def init(self):
        """Extract + shard params and optimizer state across the mesh.

        Stage >= 2 plans the per-parameter ZeRO layouts (largest
        dp-divisible dim, flat-pad fallback) and the gather/scatter
        buckets; stage 3 places params SHARDED at rest."""
        from jax.sharding import NamedSharding, PartitionSpec

        params = {k: v.data for k, v in self.model.state_dict().items()}
        if self.zero_stage >= 2:
            return self._init_zero(params)
        p_sh = self.param_rule.shardings(params, self.mesh)
        params = {
            k: jax.device_put(v, p_sh[k]) for k, v in params.items()
        }
        state = self.fopt.init_state(params)
        s_sh = zero_shard_state(
            self.fopt.state_shapes(params), params, self.mesh, self.zero_stage
        )
        state = {
            k: {s: jax.device_put(v, s_sh[k][s]) for s, v in st.items()}
            for k, st in state.items()
        }
        step_no = jax.device_put(
            jnp.zeros((), jnp.int32),
            NamedSharding(self.mesh.mesh, PartitionSpec()),
        )
        self._shardings = {
            "params": p_sh,
            "opt": s_sh,
            "step": NamedSharding(self.mesh.mesh, PartitionSpec()),
        }
        return {"params": params, "opt": state, "step": step_no}

    def _init_zero(self, params):
        """Stage-2/3 placement from the planned ZeRO layouts."""
        from jax.sharding import NamedSharding, PartitionSpec

        from . import zero as zero_mod

        mesh, dp = self.mesh, self.mesh.axis_size("dp")
        lay = self._zero_layouts = zero_mod.plan_layouts(params, dp)
        repl = NamedSharding(mesh.mesh, PartitionSpec())

        def named(spec):
            return NamedSharding(mesh.mesh, spec)

        p_sh = {}
        for name, a in params.items():
            if self.zero_stage >= 3 and lay[name].sharded:
                p_sh[name] = named(lay[name].spec())
            else:
                p_sh[name] = repl
        params = {k: jax.device_put(v, p_sh[k]) for k, v in params.items()}
        state = self.fopt.init_state(params)
        s_sh = {}
        for name in params:
            s_sh[name] = {}
            for slot in self.fopt.moment_slots:
                s_sh[name][slot] = (named(lay[name].spec())
                                    if lay[name].sharded else repl)
            for slot in self.fopt.pow_slots:
                s_sh[name][slot] = repl
        state = {
            k: {s: jax.device_put(v, s_sh[k][s]) for s, v in st.items()}
            for k, st in state.items()
        }
        step_no = jax.device_put(jnp.zeros((), jnp.int32), repl)
        self._shardings = {"params": p_sh, "opt": s_sh, "step": repl}
        return {"params": params, "opt": state, "step": step_no}

    def _batch_sharding(self, batch):
        from jax.sharding import NamedSharding, PartitionSpec

        out = {}
        for k, v in batch.items():
            if k in self.batch_specs:
                spec = PartitionSpec(*self.batch_specs[k])
            else:
                spec = [None] * np.ndim(v)
                if np.ndim(v) >= 1 and v.shape[0] % max(1, self.mesh.axis_size("dp")) == 0:
                    spec[0] = "dp"
                if (
                    np.ndim(v) >= 2
                    and self.mesh.axis_size("sp") > 1
                    and v.shape[1] % self.mesh.axis_size("sp") == 0
                ):
                    spec[1] = "sp"
                spec = PartitionSpec(*spec)
            out[k] = NamedSharding(self.mesh.mesh, spec)
        return out

    # -- the traced step -------------------------------------------------
    def _make_loss_of(self):
        """The pure ``loss_of(params, batch, key) -> scalar`` closure:
        temporarily rebinds the model's VarBase data to the traced
        param arrays and runs the user's dygraph loss_fn."""
        from ..fluid.dygraph.tracer import Tracer
        from ..fluid.dygraph.varbase import VarBase

        model, loss_fn = self.model, self.loss_fn

        def loss_of(params, batch, key):
            old = framework._dygraph_tracer
            tracer = Tracer()
            tracer._base_key = key
            framework._dygraph_tracer = tracer
            try:
                sd = model.state_dict()
                for vb in sd.values():
                    tracer.register_var(vb)
                saved = {}
                for name, arr in params.items():
                    var = sd[name]
                    saved[name] = var.data
                    var.data = arr
                try:
                    wrapped = {
                        k: VarBase(v, stop_gradient=True)
                        for k, v in batch.items()
                    }
                    loss = loss_fn(model, wrapped)
                finally:
                    for name, arr in saved.items():
                        sd[name].data = arr
                return loss.data if isinstance(loss, VarBase) else loss
            finally:
                framework._dygraph_tracer = old

        if self.remat:
            loss_of = jax.checkpoint(loss_of, static_argnums=())
        return loss_of

    def _make_grad_fn(self):
        """``grad_fn(params, batch, key) -> (loss, grads)`` with the
        bf16-AMP wrap applied (fp32 master params; AD transposes the
        cast so grads arrive fp32 for the update ops)."""
        loss_of = self._make_loss_of()
        if self.amp == "bf16":
            # bf16 compute / fp32 master params (SURVEY §2.3 AMP row:
            # the TPU equivalent of decorator.py:218 needs no loss
            # scaling).
            def amp_loss(p32, batch, key):
                # params only: batch tensors (labels, loss weights)
                # keep fp32 — float MODEL inputs meet bf16 params at
                # the op level (conv lowering aligns input dtype to
                # the filter, the AMP white-list behavior)
                p16 = jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16)
                    if x.dtype == jnp.float32 else x, p32)
                return loss_of(p16, batch, key).astype(jnp.float32)

            return jax.value_and_grad(amp_loss)
        return jax.value_and_grad(loss_of)

    def _split_micro(self, batch):
        """Reshape every batch entry [B, ...] -> [k, B/k, ...] for the
        accumulation scan; validates divisibility loudly."""
        acc = self.accumulate_steps
        micro = {}
        for k, v in batch.items():
            if v.ndim < 1 or v.shape[0] % acc:
                raise ValueError(
                    "accumulate_steps=%d does not divide batch dim %s of "
                    "feed %r (every batch entry needs a leading batch "
                    "dim divisible by accumulate_steps%s)" % (
                        acc, v.shape[:1], k,
                        " x dp" if self.zero_stage >= 2 else ""))
            micro[k] = v.reshape((acc, v.shape[0] // acc) + v.shape[1:])
        return micro

    def _accumulate(self, grad_fn, params, batch, key):
        """lax.scan over k microbatches: grads accumulate LOCALLY in
        f32 carries (no collective in the scan body — at stage >= 2 the
        single reduce-scatter happens after the scan, so gradient sync
        runs exactly once per outer step), loss/grads are the k-mean —
        numerically the large-batch step up to summation order for
        mean-reduced losses (ratio-normalized losses average per
        microbatch; see the class docstring's reduction convention)."""
        acc = self.accumulate_steps
        micro = self._split_micro(batch)

        def mstep(carry, xs):
            i, mb = xs
            l, g = grad_fn(params, mb, jax.random.fold_in(key, i))
            lsum, gsum = carry
            gsum = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), gsum, g)
            return (lsum + l.astype(jnp.float32), gsum), None

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (lsum, gsum), _ = jax.lax.scan(
            mstep, (jnp.zeros((), jnp.float32), zeros),
            (jnp.arange(acc), micro))
        return lsum / acc, jax.tree.map(lambda g: g / acc, gsum)

    def _losses_and_grads(self, grad_fn, params, batch, key):
        with jax.named_scope("loss_and_grad"):
            if self.accumulate_steps > 1:
                return self._accumulate(grad_fn, params, batch, key)
            return grad_fn(params, batch, key)

    def _build(self, batch):
        """The GSPMD step (zero_stage <= 1): one jit, XLA inserts the
        gradient all-reduce from sharding propagation."""
        fopt = self.fopt
        lr = self.fopt.learning_rate
        grad_fn = self._make_grad_fn()
        prng_impl = self.prng_impl
        me = self

        def train_step(train_state, batch):
            params = train_state["params"]
            key = jax.random.fold_in(
                jax.random.key(0, impl=prng_impl), train_state["step"]
            )
            lr_t = lr(train_state["step"]) if callable(lr) else lr
            loss, grads = me._losses_and_grads(grad_fn, params, batch, key)
            new_params, new_opt = fopt.apply(
                params, grads, train_state["opt"], lr_t
            )
            return (
                {
                    "params": new_params,
                    "opt": new_opt,
                    "step": train_state["step"] + 1,
                },
                loss,
            )

        from jax.sharding import NamedSharding, PartitionSpec

        state_sh = {
            "params": self._shardings["params"],
            "opt": self._shardings["opt"],
            "step": self._shardings["step"],
        }
        batch_sh = self._batch_sharding(batch)
        loss_sh = NamedSharding(self.mesh.mesh, PartitionSpec())
        return jax.jit(
            train_step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, loss_sh),
            donate_argnums=(0,),
        )

    def _build_zero(self, batch):
        """The explicit-communication step (zero_stage >= 2).

        One jit around a dp `shard_map` body plus a thin replication
        epilogue.  In the body every tensor works in FLAT shard space
        (`distributed.zero` layouts):

          1. stage 3 all-gathers the param buckets just-in-time;
          2. per-rank grads (optionally scan-accumulated) are bucketed
             and reduce-scattered (ONE ``psum_scatter`` per chunk,
             mean-scaled) — never all-reduced;
          3. the optimizer update runs on the local 1/N flat shards
             (beta-pow scalars advance OUTSIDE via their replicated
             recurrence — see `FunctionalOptimizer.advance_pow`);
          4. updated tensors that must re-replicate (stage-2 params,
             flat-fallback params/moments) leave the body as SHARDED
             bucket flats; the epilogue's `with_sharding_constraint`
             turns each into one all-gather XLA schedules — so the
             compiled HLO carries per-bucket reduce-scatter/all-gather
             pairs and only scalar all-reduces (the loss mean).
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from . import zero as zero_mod

        mesh = self.mesh
        dp = mesh.axis_size("dp")
        stage = self.zero_stage
        fopt = self.fopt
        lr = self.fopt.learning_rate
        grad_fn = self._make_grad_fn()
        prng_impl = self.prng_impl
        me = self
        lay = self._zero_layouts
        names = list(lay)
        moment_slots = fopt.moment_slots
        pow_slots = fopt.pow_slots

        # bucket plans (param order = forward consumption order)
        grad_buckets = zero_mod.plan_buckets(
            lay, names, self.gather_chunk_bytes)
        fwd_gather_buckets = zero_mod.plan_buckets(
            lay, [n for n in names if lay[n].sharded],
            self.gather_chunk_bytes) if stage >= 3 else []
        # reassembly: tensors whose NEW value must be replicated again —
        # stage-2 params, flat-fallback params (any stage), and
        # flat-fallback moments.  Keys are (name, slot-or-None).
        reasm_keys = []
        for n in names:
            if stage < 3 or not lay[n].sharded:
                reasm_keys.append((n, None))
        for n in names:
            if not lay[n].sharded:
                for slot in moment_slots:
                    reasm_keys.append((n, slot))
        reasm_lay = {k: lay[k[0]] for k in reasm_keys}
        reasm_buckets = zero_mod.plan_buckets(
            reasm_lay, reasm_keys, self.gather_chunk_bytes)

        def bucket_concat(flats_by_key, bucket, layouts):
            segs = [flats_by_key[k] for k in bucket]
            return segs[0] if len(segs) == 1 else jnp.concatenate(segs)

        def bucket_split(flat, bucket, layouts):
            offs, _total = zero_mod.bucket_offsets(layouts, bucket)
            return {k: flat[o:o + c] for k, o, c in offs}

        def body(train_state, batch):
            params_in = train_state["params"]
            opt_in = train_state["opt"]
            step_no = train_state["step"]
            idx = jax.lax.axis_index("dp")
            # per-rank key: the dp index folds in so stochastic ops
            # (dropout) draw independent masks per shard
            key = jax.random.fold_in(
                jax.random.key(0, impl=prng_impl), step_no)
            key = jax.random.fold_in(key, idx)
            lr_t = lr(step_no) if callable(lr) else lr

            # 1. full params for the forward
            full = {}
            if stage >= 3:
                shard_flats = {
                    n: lay[n].shard_to_flat(params_in[n])
                    for n in names if lay[n].sharded}
                for bucket in fwd_gather_buckets:
                    cat = bucket_concat(shard_flats, bucket, lay)
                    gathered = jax.lax.all_gather(
                        cat, "dp", axis=0, tiled=True)
                    rows = gathered.reshape(dp, -1)
                    for k2, o, c in zero_mod.bucket_offsets(lay, bucket)[0]:
                        full[k2] = lay[k2].rows_to_full(rows[:, o:o + c])
                for n in names:
                    if not lay[n].sharded:
                        full[n] = params_in[n]
            else:
                full = dict(params_in)

            # 2. local grads (scan-accumulated), then bucketed
            #    reduce-scatter — the ONLY gradient sync.  Wire format
            #    per bucket: [dp, flat_i] rows hstacked to [dp, T] and
            #    flattened row-major, so contiguous segment r is rank
            #    r's shard of EVERY bucket member (what tiled
            #    psum_scatter hands rank r)
            loss, grads = me._losses_and_grads(grad_fn, full, batch, key)
            loss = jax.lax.psum(loss, "dp") / dp
            grad_rows = {n: lay[n].full_to_rows(grads[n]) for n in names}
            gshards = {}
            for bucket in grad_buckets:
                segs = [grad_rows[k] for k in bucket]
                cat = (segs[0] if len(segs) == 1
                       else jnp.concatenate(segs, axis=1))
                sh = jax.lax.psum_scatter(
                    cat.reshape(-1), "dp", scatter_dimension=0,
                    tiled=True) / dp
                gshards.update(bucket_split(sh, bucket, lay))

            # 3. flat-shard optimizer update
            p_flat, g_flat, s_flat = {}, {}, {}
            for n in names:
                if lay[n].sharded:
                    src = (params_in[n] if stage >= 3
                           else lay[n].local_flat(full[n], idx))
                    p_flat[n] = (lay[n].shard_to_flat(src)
                                 if stage >= 3 else src)
                else:
                    p_flat[n] = lay[n].local_flat(full[n], idx)
                g_flat[n] = gshards[n]
                st = {}
                for slot in moment_slots:
                    if lay[n].sharded:
                        st[slot] = lay[n].shard_to_flat(opt_in[n][slot])
                    else:
                        st[slot] = lay[n].local_flat(opt_in[n][slot], idx)
                for slot in pow_slots:
                    st[slot] = opt_in[n][slot]   # replicated scalar
                s_flat[n] = st
            new_p_flat, new_s_flat = fopt.apply(
                p_flat, g_flat, s_flat, lr_t)

            # 4. route outputs: sharded-at-rest tensors leave in shard
            #    orientation; replication-bound tensors leave as bucket
            #    flats for the epilogue's all-gathers
            out_params, out_moments = {}, {}
            reasm_flats = {}
            for n in names:
                if stage >= 3 and lay[n].sharded:
                    out_params[n] = lay[n].flat_to_shard(new_p_flat[n])
                else:
                    reasm_flats[(n, None)] = new_p_flat[n]
                om = {}
                for slot in moment_slots:
                    if lay[n].sharded:
                        om[slot] = lay[n].flat_to_shard(
                            new_s_flat[n][slot])
                    else:
                        reasm_flats[(n, slot)] = new_s_flat[n][slot]
                out_moments[n] = om
            reasm_out = [
                bucket_concat(reasm_flats, bucket, reasm_lay)
                for bucket in reasm_buckets]
            return out_params, out_moments, reasm_out, loss

        # specs ---------------------------------------------------------
        def state_spec(sh):
            return sh.spec

        p_specs = {n: state_spec(self._shardings["params"][n])
                   for n in names}
        o_specs = {n: {s: state_spec(sh)
                       for s, sh in self._shardings["opt"][n].items()}
                   for n in names}
        batch_specs = {k: sh.spec
                       for k, sh in self._batch_sharding(batch).items()}
        in_specs = ({"params": p_specs, "opt": o_specs, "step": P()},
                    batch_specs)
        out_p_specs = {n: lay[n].spec() for n in names
                       if stage >= 3 and lay[n].sharded}
        out_m_specs = {n: {s: lay[n].spec() for s in moment_slots
                           if lay[n].sharded} for n in names}
        out_specs = (out_p_specs, out_m_specs,
                     [P("dp") for _ in reasm_buckets], P())

        mapped = jax.shard_map(
            body, mesh=mesh.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)

        repl = NamedSharding(mesh.mesh, P())

        def train_step(train_state, batch):
            out_params, out_moments, reasm_out, loss = mapped(
                train_state, batch)
            # per-bucket all-gathers: one resharding constraint per
            # chunk, independently schedulable/overlappable by XLA
            new_params = dict(out_params)
            new_opt = {n: dict(out_moments[n]) for n in names}
            for bucket, flat in zip(reasm_buckets, reasm_out):
                full_flat = jax.lax.with_sharding_constraint(flat, repl)
                rows = full_flat.reshape(dp, -1)
                for k2, o, c in zero_mod.bucket_offsets(
                        reasm_lay, bucket)[0]:
                    n, slot = k2
                    val = reasm_lay[k2].rows_to_full(rows[:, o:o + c])
                    if slot is None:
                        new_params[n] = val
                    else:
                        new_opt[n][slot] = val
            # beta-pow scalars: replicated recurrence, no collective
            for n in names:
                for slot in pow_slots:
                    new_opt[n][slot] = fopt.advance_pow(
                        slot, train_state["opt"][n][slot])
            return (
                {"params": new_params, "opt": new_opt,
                 "step": train_state["step"] + 1},
                loss,
            )

        state_sh = {
            "params": self._shardings["params"],
            "opt": self._shardings["opt"],
            "step": self._shardings["step"],
        }
        batch_sh = self._batch_sharding(batch)
        return jax.jit(
            train_step,
            in_shardings=(state_sh, batch_sh),
            out_shardings=(state_sh, repl),
            donate_argnums=(0,),
        )

    def place_batch(self, batch):
        """Pre-place a host batch on the mesh with the step's feed
        shardings (double-buffer staging: call on batch t+1 while step t
        runs; __call__ then sees correctly-placed arrays and skips the
        transfer)."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        sh = self._batch_sharding(batch)
        return {k: jax.device_put(v, sh[k]) for k, v in batch.items()}

    # the executable-cache key for one batch signature — writer
    # (__call__) and reader (cost_analysis) share the one canonical
    # builder in observability.xla_cost
    _batch_sig = staticmethod(feed_signature)

    def cost_analysis(self, train_state, batch):
        """XLA `cost_analysis()` of the compiled step executable for this
        batch signature (flops / bytes_accessed per step as the fused HLO
        reports them — the measured-MFU numerator).  `lower().compile()`
        reuses the already-built executable after the first real step and
        only reads avals, so donated/deleted buffers are fine.  Returns
        None when nothing was compiled for this signature yet or the
        backend reports no costs (attribution is telemetry, never an
        error source)."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        step_fn = self._step_fns.get(self._batch_sig(batch))
        if step_fn is None:
            return None
        from ..observability.xla_cost import cost_of_jitted

        return cost_of_jitted(step_fn, train_state, batch)

    def _build_step(self, batch):
        if self.zero_stage >= 2:
            return self._build_zero(batch)
        return self._build(batch)

    def compiled_hlo(self, train_state, batch):
        """Optimized-HLO text of the compiled step executable for this
        batch signature — the ground truth the collective assertions
        and the comm cost model validate against.  The first call per
        signature pays ONE extra XLA compilation (the AOT
        ``lower().compile()`` path is not served by the jit call
        cache); the text is memoized so repeat calls — including
        `collective_stats` — are free.  Attribution tooling, never on
        the step path; only avals are read, so donated/deleted state
        buffers are fine.  None when nothing was compiled for this
        signature yet."""
        batch = {k: jnp.asarray(v) for k, v in batch.items()}
        sig = self._batch_sig(batch)
        if sig in self._hlo_texts:
            return self._hlo_texts[sig]
        step_fn = self._step_fns.get(sig)
        if step_fn is None:
            return None
        try:
            text = step_fn.lower(train_state, batch).compile().as_text()
            self._hlo_texts[sig] = text
            return text
        except Exception as e:
            # attribution stays non-fatal, but the cause must surface —
            # callers assert on None and would otherwise have no trail
            import warnings

            warnings.warn(
                "compiled_hlo: lower/compile of the step failed "
                "(%s: %s) — collective stats unavailable"
                % (type(e).__name__, e))
            return None

    def collective_stats(self, train_state, batch):
        """Per-collective counts + bytes extracted from the compiled
        HLO (`analysis.comm.hlo_collective_stats` over the dp size):
        ``{kind: {count, result_bytes, wire_bytes, entry_count}}``.
        None when the executable or its HLO is unavailable."""
        hlo = self.compiled_hlo(train_state, batch)
        if hlo is None:
            return None
        from ..analysis import comm as comm_mod

        return comm_mod.hlo_collective_stats(
            hlo, self.mesh.axis_size("dp"))

    def comm_estimate(self):
        """The static per-step collective-traffic prediction for this
        step's layouts (`distributed.zero.zero_comm_estimate`); None on
        the GSPMD path (stage <= 1: XLA owns collective placement) or
        before init()."""
        if self.zero_stage < 2 or self._zero_layouts is None:
            return None
        from . import zero as zero_mod

        return zero_mod.zero_comm_estimate(
            self._zero_layouts, self.zero_stage,
            self.mesh.axis_size("dp"),
            chunk_bytes=self.gather_chunk_bytes,
            state_slots_per_param=len(self.fopt.moment_slots))

    def __call__(self, train_state, batch):
        t0 = time.perf_counter()
        with _trace.span("train.step_dispatch", cat="train"):
            batch = {k: jnp.asarray(v) for k, v in batch.items()}
            sig = self._batch_sig(batch)
            step_fn = self._step_fns.get(sig)
            if step_fn is None:
                if self._shardings is None:
                    raise RuntimeError("call init() before the first step")
                step_fn = self._step_fns[sig] = self._build_step(batch)
            with _trace.span("train.batch_put", cat="train"):
                batch_sh = self._batch_sharding(batch)
                batch = {k: jax.device_put(v, batch_sh[k])
                         for k, v in batch.items()}
            out = step_fn(train_state, batch)
        self._m_dispatch_ms.observe((time.perf_counter() - t0) * 1e3)
        return out
