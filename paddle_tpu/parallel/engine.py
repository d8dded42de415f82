"""Sharded train-step builder — the GSPMD-native auto-parallel Engine.

Ref: python/paddle/distributed/auto_parallel/engine.py:58 (Engine.fit :811,
_build :515 → _parallel :700) + parallelizer_v2.py: the reference completes
dist attrs, slices per-rank programs (Partitioner) and inserts reshard comm.
Here all three steps are XLA's job: we (1) collect per-parameter
PartitionSpecs (layer-provided, e.g. ColumnParallelLinear, or FSDP-style
auto-sharding), (2) jit the (loss, grads, opt-update) step with those
shardings as in/out shardings over the mesh, (3) let GSPMD propagate and
insert collectives. ZeRO == param/opt-state sharding over the "sharding"
axis (ref dygraph_sharding_optimizer.py:29, group_sharded_stage{2,3}.py).
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..faults import NULL_INJECTOR, StepFault
from ..framework.core import Parameter, Tensor, no_grad_ctx
from ..jit import functional_call, state_values
from .api import _filter_spec, auto_shard_spec, mesh_context


def param_specs(model, mesh: Mesh, fsdp: bool = False, fsdp_axis: str = "sharding"
                ) -> Dict[str, P]:
    """fsdp=True applies the canonical ZeRO-3 layout policy, shared with
    distributed.sharding (ref group_sharded_stage3.py:60 — param sharding
    with fwd allgather, which GSPMD emits automatically): a parameter
    without a layer ``pspec`` takes ``auto_shard_spec``; one that carries a
    pspec (every large Llama weight does, for tensor parallelism) keeps it
    and gains ``fsdp_axis`` on its largest still-free, evenly divisible dim
    (``_add_fsdp_axis``) — otherwise fsdp would leave most bytes
    replicated. Even splits only: these specs are applied eagerly via
    device_put in _build_state."""
    axis_size = mesh.shape[fsdp_axis] if fsdp_axis in mesh.axis_names else 1
    specs: Dict[str, P] = {}
    for name, p in model.named_parameters():
        spec = getattr(p, "pspec", None)
        if spec is None:
            spec = (auto_shard_spec(p.value.shape, axis_size, axis=fsdp_axis)
                    if fsdp else P())
            specs[name] = _filter_spec(spec, mesh)
        else:
            spec = _filter_spec(spec, mesh)
            specs[name] = (_add_fsdp_axis(spec, p.value.shape, axis_size,
                                          fsdp_axis) if fsdp else spec)
    for name, b in model.named_buffers():
        specs[name] = P()
    return specs


def _add_fsdp_axis(spec: P, shape, axis_size: int, axis: str,
                   min_size: int = 1024) -> P:
    """``spec`` with ``axis`` laid over the largest dim it leaves free and
    ``axis_size`` divides; unchanged for tiny arrays (same ``min_size`` as
    ``auto_shard_spec``), when ``axis`` is already used, or when no free
    dim divides."""
    shape = tuple(shape)
    parts = list(spec) + [None] * (len(shape) - len(spec))
    used = {a for p in parts if p is not None
            for a in ((p,) if isinstance(p, str) else p)}
    if (axis_size <= 1 or axis in used
            or int(np.prod(shape, dtype=np.int64)) < min_size):
        return spec
    free = [i for i in range(len(shape))
            if parts[i] is None and shape[i] % axis_size == 0]
    if not free:
        return spec
    parts[max(free, key=lambda i: shape[i])] = axis
    return P(*parts)


def _sharding_of(mesh, spec):
    return NamedSharding(mesh, spec)


class ParallelEngine:
    """Owns sharded params + optimizer state and a compiled train step.

    Stateful on purpose (donated buffers): eager model params are copied in
    once, updated on-device every step, and synced back on demand
    (`sync_to_model`) for checkpointing through the normal state_dict path.
    """

    def __init__(self, model, optimizer=None, loss_fn: Optional[Callable] = None,
                 mesh: Optional[Mesh] = None, fsdp: bool = False, remat: bool = False,
                 remat_policy: Optional[str] = "dots", batch_spec: Any = P("data"),
                 donate: bool = True, abstract: bool = False,
                 offload_opt_state: bool = False,
                 alias_model_params: bool = False,
                 grad_accum: int = 1,
                 injector=NULL_INJECTOR,
                 telemetry=None):
        """abstract=True keeps params/opt-state as ShapeDtypeStructs — the
        step can be .lower()ed (AOT partitioning validation at any scale)
        but not executed.

        grad_accum=k splits each train_batch into k microbatches scanned
        inside the compiled step (leading batch dim must divide by k), with
        ONE optimizer update on the mean gradient — amortizes the
        optimizer/PCIe cost on the offload path (ref
        gradient_merge_optimizer.py; PT_ACCUM_DTYPE sets the accumulator
        dtype, default float32).

        offload_opt_state=True parks the optimizer moments in host RAM
        (pinned_host memory) between steps — the compiled step streams them
        d2h/h2d through PCIe, freeing ~8 bytes/param of HBM so a ~2-3B
        AdamW config fits one 16 GB chip (ref group_sharded_stage3.py:60
        cpu_offload semantics, done as XLA memory kinds instead of tensor
        .cpu() hooks). Single-device path only.
        """
        from ..distributed.collective import get_global_mesh

        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.mesh = mesh or get_global_mesh()
        if self.mesh is None:
            devs = np.array(jax.devices()[:1])
            self.mesh = Mesh(devs.reshape(1), ("data",))
        self.fsdp = fsdp
        self.remat = remat
        self.remat_policy = remat_policy
        self.batch_spec = batch_spec
        self._donate = donate
        self._abstract = abstract
        self._offload_opt = offload_opt_state
        self.grad_accum = int(grad_accum)
        if self.grad_accum < 1:
            raise ValueError(f"grad_accum must be >= 1, got {grad_accum}")
        # alias_model_params=True skips the defensive params copy (single-
        # device path): saves a full param-size HBM allocation on big
        # models, at the cost that the eager model is INVALID until
        # sync_to_model (donation consumes the shared buffers)
        self._alias_params = alias_model_params
        self.injector = injector or NULL_INJECTOR
        # optional TrainTelemetry (paddle_tpu/telemetry.py). None (the
        # default) keeps train_batch free of timestamp reads and of the
        # per-step block_until_ready the device_wait span needs.
        self.telemetry = telemetry
        if offload_opt_state and self.mesh.size > 1:
            raise NotImplementedError(
                "offload_opt_state is single-device; multi-chip runs shard "
                "the state over the mesh instead (ZeRO)")
        self._build_state()
        self._train_step = None
        self._eval_step = None

    @staticmethod
    def _place(v, sharding):
        """Materialize a host value under `sharding`. Multi-process: the
        mesh spans non-addressable devices, so assemble the global array
        from the (identical-per-process) host value — each process
        materializes only its addressable shards (ref parallel.py:108
        sync_params broadcast; identical host values replace the
        broadcast)."""
        if jax.process_count() <= 1:
            # copy first: device_put may alias the source buffer (zero-copy
            # same-device path), and the engine donates its state every
            # step — an aliased source (the model's live eager param)
            # would be deleted by the first step, breaking the "params are
            # copied in once" contract
            if isinstance(v, jax.Array):
                v = jnp.copy(v)
            return jax.device_put(v, sharding)
        arr = np.asarray(v)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    @staticmethod
    def _host_sharding():
        from jax.sharding import SingleDeviceSharding

        dev = jax.devices()[0]
        kinds = {m.kind for m in dev.addressable_memories()}
        if "pinned_host" not in kinds:
            # the CPU backend has no device-placement custom call at all
            # (annotate_device_placement unregistered) — offload is a
            # TPU-backend feature, verified on chip (tests_tpu/)
            raise NotImplementedError(
                f"offload_opt_state needs a backend with pinned_host "
                f"memory; this backend has {sorted(kinds)}")
        return SingleDeviceSharding(dev, memory_kind="pinned_host")

    # ------------------------------------------------------------------ state
    def _build_state(self):
        mesh = self.mesh
        # multi-process: a committed single-device jnp scalar can't enter a
        # jit spanning the global mesh; a host value is treated as
        # replicated (identical across processes by construction). Lives
        # here (not build_train_step) so engine_state_dict works on a
        # freshly built engine; set_engine_state may overwrite it.
        self._step_count = (np.zeros((), np.int32)
                            if jax.process_count() > 1
                            else jnp.zeros((), jnp.int32))
        # single-device mesh: keep plain (unsharded) arrays — NamedSharding
        # inputs route jit through the SPMD partitioner, which compiles a
        # measurably worse program around Pallas custom calls (6x step time
        # at S=16k on one v5e); GSPMD buys nothing with one device anyway
        self._spmd = mesh.size > 1
        self.specs = param_specs(self.model, mesh, fsdp=self.fsdp)
        vals = state_values(self.model)
        self._trainable = {name for name, p in self.model.named_parameters()
                           if p.trainable}
        if self._abstract:
            self.params = {
                name: jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=_sharding_of(mesh, self.specs.get(name, P())))
                for name, v in vals.items()
            }
            if self.optimizer is not None:
                train = {n: v for n, v in self.params.items()
                         if n in self._trainable}
                st = jax.eval_shape(self.optimizer.init_state, train)
                # re-attach shardings per owning param
                self.opt_state = {
                    n: {k: jax.ShapeDtypeStruct(
                        s.shape, s.dtype,
                        sharding=_sharding_of(mesh, self.specs.get(n, P())))
                        for k, s in slots.items()}
                    for n, slots in st.items()
                }
            else:
                self.opt_state = {}
            return
        if not self._spmd:
            # copy: self.params gets donated every step; aliasing the model's
            # live Parameter buffers would invalidate eager use of the model
            # (model(x), p.value) until sync_to_model
            self.params = (dict(vals) if self._alias_params else
                           {name: jnp.copy(v) for name, v in vals.items()})
            train = {n: v for n, v in self.params.items()
                     if n in self._trainable}
            if self.optimizer is None:
                self.opt_state = {}
            elif self._offload_opt:
                if getattr(self.optimizer, "_mt_active", lambda: False)():
                    raise ValueError(
                        "offload_opt_state and PT_MT_ADAMW are mutually "
                        "exclusive (the flat state has no per-param layout "
                        "to stream); unset one")
                # init the slots INSIDE a jit whose out_shardings are host
                # memory: materializing the full f32 state on device first
                # (19 GB at 2.4B) is exactly what offload must avoid
                host = self._host_sharding()
                sds = jax.eval_shape(self.optimizer.init_state, train)
                self.opt_state = jax.jit(
                    self.optimizer.init_state,
                    out_shardings=jax.tree.map(lambda _: host, sds))(train)
            else:
                self.opt_state = self.optimizer.init_state(train)
            return
        multiproc = jax.process_count() > 1
        self.params = {
            name: self._place(v, _sharding_of(mesh, self.specs.get(name, P())))
            for name, v in vals.items()
        }
        if self.optimizer is not None:
            train_params = {n: v for n, v in self.params.items() if n in self._trainable}
            # opt state shards like its param (ZeRO-1/2: ref
            # dygraph_sharding_optimizer.py — state lives sharded)
            state_sh = {
                n: _sharding_of(mesh, self.specs.get(n, P()))
                for n in train_params}
            if multiproc:
                # eager ops on global arrays with non-addressable shards are
                # rejected; init through jit so XLA produces sharded state
                sds = jax.eval_shape(self.optimizer.init_state, train_params)
                out_sh = {n: {k: state_sh[n] for k in slots}
                          for n, slots in sds.items()}
                self.opt_state = jax.jit(
                    self.optimizer.init_state,
                    out_shardings=out_sh)(train_params)
            else:
                state = self.optimizer.init_state(train_params)
                self.opt_state = {
                    n: {k: jax.device_put(v, state_sh[n])
                        for k, v in slots.items()}
                    for n, slots in state.items()
                }
        else:
            self.opt_state = {}

    # ------------------------------------------------------------- train step
    def _loss_from_batch(self, params, batch, state_out=None):
        """state_out: dict capturing buffer values the forward reassigned
        (BN running stats etc.) so the jitted step can carry them."""
        model, loss_fn = self.model, self.loss_fn

        def call(p, *args):
            with mesh_context(self.mesh):
                out = functional_call(model, p, *[Tensor(a) for a in args],
                                      mutated_state=state_out)
            return out

        if isinstance(batch, dict):
            inputs = batch.get("inputs", ())
            labels = batch.get("labels", ())
            inputs = inputs if isinstance(inputs, (list, tuple)) else (inputs,)
            labels = labels if isinstance(labels, (list, tuple)) else (labels,)
        else:
            *inputs, label = batch
            labels = (label,)
        if loss_fn is None:
            # model computes its own loss (e.g. fused lm-head+CE path where
            # logits must never materialize): forward(*inputs, *labels) -> loss
            out = call(params, *inputs, *labels)
            out = out[0] if isinstance(out, (list, tuple)) else out
            return out.value if isinstance(out, Tensor) else out
        out = call(params, *inputs)
        outs = out if isinstance(out, (list, tuple)) else (out,)
        with mesh_context(self.mesh):
            loss = loss_fn(*outs, *[Tensor(l) for l in labels])
        return loss.value if isinstance(loss, Tensor) else loss

    @staticmethod
    def _raw(v):
        return v.value if isinstance(v, Tensor) else v

    def _assemble_batch(self, batch):
        """Device-ready batch tuple, shared by train_batch/eval_batch.

        Multi-process (ref test_dist_base.py:899 per-rank readers): each
        process passes its LOCAL shard of the batch; the global array is
        assembled against the batch sharding without any cross-host gather
        of example data. On either path an unevenly-divisible batch is an
        error — pad to the bucket (io.LengthBucketBatchSampler) instead."""
        def spec_of(i):
            # PartitionSpec subclasses tuple: a bare P("data") must apply
            # whole to every batch element, not be indexed into per-element
            # axis names (which _filter_spec would then iterate char-wise)
            if isinstance(self.batch_spec, P):
                return self.batch_spec
            return (self.batch_spec[i]
                    if isinstance(self.batch_spec, (list, tuple))
                    else self.batch_spec)

        if self._spmd and jax.process_count() > 1:
            out = []
            for i, b in enumerate(batch):
                arr = np.asarray(b.value if isinstance(b, Tensor) else b)
                spec = _filter_spec(spec_of(i), self.mesh)
                try:
                    out.append(jax.make_array_from_process_local_data(
                        _sharding_of(self.mesh, spec), arr))
                except ValueError as e:
                    raise ValueError(
                        f"per-process batch shard of shape {arr.shape} "
                        f"does not assemble evenly under spec {spec} on "
                        f"mesh {dict(self.mesh.shape)}; pad the local "
                        f"shard to an even split (see io bucketing "
                        f"helpers)") from e
            return tuple(out)
        batch_vals = tuple(
            b.value if isinstance(b, Tensor) else jnp.asarray(b)
            for b in batch)
        if self._spmd:
            batch_vals = tuple(
                jax.device_put(b, self._batch_sharding(b, spec_of(i)))
                for i, b in enumerate(batch_vals))
        return batch_vals

    def _batch_sharding(self, arr, spec):
        """NamedSharding for one batch array under ``spec``. A dim the
        spec's mesh axes do not divide is an error: replicating the batch
        instead would make every device compute all of it, silently."""
        spec = _filter_spec(spec, self.mesh)
        dims = []
        for i, ax in enumerate(spec):
            if ax is None or i >= arr.ndim:
                dims.append(None)
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            size = int(np.prod([self.mesh.shape[a] for a in axes]))
            if arr.shape[i] % size:
                raise ValueError(
                    f"batch array of shape {tuple(arr.shape)} does not "
                    f"split evenly under batch_spec {spec} on mesh "
                    f"{dict(self.mesh.shape)}: dim {i} ({arr.shape[i]}) is "
                    f"not a multiple of {size}. Pad the batch (see the io "
                    f"bucketing helpers) or pass a batch_spec that fits.")
            dims.append(ax)
        return _sharding_of(self.mesh, P(*dims))

    def build_train_step(self):
        mesh = self.mesh
        opt = self.optimizer

        def step_fn(params, opt_state, step_count, lr, batch):
            train = {n: v for n, v in params.items() if n in self._trainable}
            frozen = {n: v for n, v in params.items() if n not in self._trainable}

            def loss_of(tr, mb, frozen_vals):
                # aux = buffers the forward reassigned (BN running stats):
                # captured from the eager side effect and carried as a jit
                # output so the compiled path matches eager BN semantics
                mutated = {}
                loss = self._loss_from_batch({**tr, **frozen_vals}, mb,
                                             state_out=mutated)
                new_bufs = {n: self._raw(v) for n, v in mutated.items()
                            if n not in self._trainable}
                return loss, new_bufs

            if self.remat:
                # keep MXU outputs, recompute elementwise (the reference's
                # recompute granularity is whole-layer; saving dot outputs is
                # the better HBM/FLOP tradeoff on TPU). Named policies rely
                # on the checkpoint_name annotations in models/llama.py
                # ("attn_out", "qkv", "mlp_out").
                cp = jax.checkpoint_policies
                policy = None
                if self.remat_policy == "dots":
                    policy = cp.dots_with_no_batch_dims_saveable
                elif self.remat_policy == "nothing":
                    policy = cp.nothing_saveable
                elif self.remat_policy == "save_attn":
                    policy = cp.save_only_these_names("attn_out")
                elif self.remat_policy == "save_attn_mlp":
                    policy = cp.save_only_these_names("attn_out", "mlp_out")
                elif self.remat_policy == "save_qkv_attn":
                    policy = cp.save_only_these_names("attn_out", "qkv")
                elif self.remat_policy == "offload_attn":
                    # activations ride host RAM instead of being recomputed
                    policy = cp.save_and_offload_only_these_names(
                        names_which_can_be_saved=[],
                        names_which_can_be_offloaded=["attn_out", "mlp_out"],
                        offload_src="device", offload_dst="pinned_host")
                elif self.remat_policy is not None and \
                        self.remat_policy != "none":
                    raise ValueError(
                        f"unknown remat_policy {self.remat_policy!r}")
                loss_of_ = jax.checkpoint(loss_of, policy=policy)
            else:
                loss_of_ = loss_of
            accum = self.grad_accum
            if accum > 1:
                # gradient accumulation (ref gradient_merge_optimizer.py /
                # group_sharded k-microbatch amortization): scan over k
                # microbatches, sum grads, one optimizer update — divides
                # the per-step optimizer/PCIe cost by k on the offload path
                mbs = jax.tree.map(
                    lambda b: b.reshape((accum, b.shape[0] // accum)
                                        + b.shape[1:]), batch)
                acc_dtype = jnp.dtype(
                    os.environ.get("PT_ACCUM_DTYPE", "float32"))

                def body(carry, mb_i):
                    acc_l, acc_g, frozen_cur = carry
                    # buffers (BN running stats) thread microbatch →
                    # microbatch, matching eager sequential semantics (ref
                    # gradient_merge: each micro-step runs a full forward)
                    (l, bufs), g = jax.value_and_grad(
                        loss_of_, has_aux=True)(train, mb_i, frozen_cur)
                    acc_g = jax.tree.map(
                        lambda a, gi: a + gi.astype(a.dtype), acc_g, g)
                    return ((acc_l + l.astype(jnp.float32), acc_g,
                             {**frozen_cur, **bufs}), None)

                zero_g = {n: jnp.zeros(v.shape, acc_dtype)
                          for n, v in train.items()}
                (loss_sum, grads, frozen_out), _ = jax.lax.scan(
                    body, (jnp.zeros((), jnp.float32), zero_g, frozen),
                    mbs)
                loss = loss_sum / accum
                grads = jax.tree.map(lambda g: g / accum, grads)
                # every frozen entry rides the carry (mutated-or-not is
                # only known under the trace); unchanged ones are
                # pass-through values XLA elides
                new_bufs = frozen_out
            else:
                (loss, new_bufs), grads = jax.value_and_grad(
                    loss_of_, has_aux=True)(train, batch, frozen)
            if self._offload_opt and opt_state:
                new_train, new_state = self._offloaded_update(
                    opt, train, grads, opt_state, lr, step_count + 1, loss)
            else:
                new_train, new_state = opt.pure_update(train, grads,
                                                       opt_state, lr,
                                                       step_count + 1)
            if self._spmd:
                # keep shardings stable across steps
                new_train = {
                    n: jax.lax.with_sharding_constraint(
                        v, _sharding_of(mesh, self.specs.get(n, P())))
                    for n, v in new_train.items()
                }
            frozen = {**frozen, **new_bufs}
            return {**new_train, **frozen}, new_state, step_count + 1, loss

        donate = (0, 1, 2) if self._donate else ()
        jit_kw = {}
        if self._offload_opt and self.opt_state and not hasattr(
                self.optimizer, "_apply_one") and not hasattr(
                self.optimizer, "_apply_adamw"):
            raise NotImplementedError(
                "offload_opt_state needs a per-param update rule "
                "(_apply_one/_apply_adamw)")
        if self._offload_opt and self.opt_state:
            # pin the NEW opt state back to host memory; everything else
            # (None = unspecified) stays wherever XLA puts it
            host = self._host_sharding()
            jit_kw["out_shardings"] = (
                None, jax.tree.map(lambda _: host, self.opt_state), None,
                None)
        self._train_step = jax.jit(step_fn, donate_argnums=donate, **jit_kw)
        return self._train_step

    def _offloaded_update(self, opt, train, grads, opt_state, lr, step,
                          loss):
        """Per-param optimizer update with host-resident moments, streamed
        through a WINDOWED transfer chain.

        A naive whole-tree h2d materializes every moment tensor in HBM at
        once (measured RESOURCE_EXHAUSTED at 2.4B on v5e — XLA hoists the
        transfers), defeating the offload. Here each param's moments are
        transferred, updated and sent back inside a data-dependency chain
        built from optimization_barriers:

        - h2d_i is gated on h2d_{i-1} (PCIe h2d traffic serializes) AND on
          update_{i-W} (at most W ≈ PT_OFFLOAD_WINDOW moment sets live in
          HBM). W=1 is the round-4 strict chain; W>=2 double-buffers:
          param i+1's moments stream in while param i updates and its new
          state streams OUT (h2d/d2h ride opposite PCIe directions).
        - params walk in REVERSE name order (PT_OFFLOAD_ORDER=backward,
          default): backward produces grads for the LAST layers first, so
          updates and transfers start while earlier layers' backward still
          computes instead of stalling on the first param's grad.

        Host offload still trades step time for fit (ref
        group_sharded_stage3.py:60 cpu-offload, whose point is that the
        tradeoff is tunable) — the window + order make the PCIe pipe the
        only cost, not the scheduling.
        """
        from jax.sharding import SingleDeviceSharding

        from ..optimizer.optimizer import _pure_grad_clip

        dev_s = SingleDeviceSharding(jax.devices()[0], memory_kind="device")
        host = self._host_sharding()
        apply_adamw = getattr(opt, "_apply_adamw", None)
        # same pre-update semantics as pure_update: clip, decay masking,
        # L2-as-grad for non-decoupled optimizers
        if opt._grad_clip is not None:
            grads = _pure_grad_clip(opt._grad_clip, grads)
        window = max(1, int(os.environ.get("PT_OFFLOAD_WINDOW", "2")))
        order = os.environ.get("PT_OFFLOAD_ORDER", "backward")
        if order not in ("backward", "forward"):
            raise ValueError(
                f"PT_OFFLOAD_ORDER must be 'backward' or 'forward', got "
                f"{order!r}")
        names = sorted(train)
        if order == "backward":
            names = list(reversed(names))

        def scalar_token(v):
            return jax.lax.convert_element_type(
                v.ravel()[0], jnp.float32) * 0.0

        new_train, new_state = {}, {}
        h2d_token = loss * 0.0
        update_tokens = []
        i = -1  # running index into the live (grad-bearing) params
        for n in names:
            g = grads.get(n)
            if g is None:
                new_train[n] = train[n]
                new_state[n] = opt_state.get(n, {})
                continue
            g = g.astype(jnp.float32)
            i += 1
            gate = h2d_token
            if i >= window:
                gate = gate + update_tokens[i - window]
            slots = {
                k: jax.device_put(
                    jax.lax.optimization_barrier((v, gate))[0], dev_s)
                for k, v in opt_state[n].items()}
            h2d_token = scalar_token(next(iter(slots.values())))
            if apply_adamw is not None:
                decay = opt._wd_coeff
                if opt._apply_decay_param_fun is not None and \
                        not opt._apply_decay_param_fun(n):
                    decay = 0.0
                np_, ns = apply_adamw(train[n], g, lr, step, decay, slots)
            else:
                if opt._use_l2_decay() and opt._l2_coeff:
                    g = g + opt._reg_grad(train[n].astype(jnp.float32))
                np_, ns = opt._apply_one(train[n], g, lr, step, slots)
            update_tokens.append(scalar_token(next(iter(ns.values()))))
            new_train[n] = np_
            new_state[n] = {k: jax.device_put(v, host)
                            for k, v in ns.items()}
        return new_train, new_state

    def train_batch(self, *batch):
        """Run one compiled, sharded train step; returns host loss.

        With ``telemetry`` attached, phase timestamps (host→device
        assemble, compiled dispatch, device wait) are recorded AROUND the
        compiled call — never inside it (graftlint GL010) — and the step
        blocks on the loss so ``device_wait`` measures real device time.
        """
        tel = self.telemetry
        if self._train_step is None:
            self.build_train_step()
        lr = self.optimizer.get_lr()
        t0 = tel.clock() if tel is not None else 0.0
        batch_vals = self._assemble_batch(batch)
        t_h2d = tel.clock() if tel is not None else 0.0
        if self.grad_accum > 1:
            for b in batch_vals:
                if b.shape[0] % self.grad_accum:
                    raise ValueError(
                        f"grad_accum={self.grad_accum} needs the leading "
                        f"batch dim to divide evenly, got {b.shape}")
        spec = self.injector.fire("train_step")
        if spec is not None:
            # fire BEFORE the compiled dispatch: params/opt_state have not
            # been donated yet, so the caller may retry this step verbatim
            if spec.kind == "fatal":
                raise RuntimeError("injected fatal train-step fault")
            raise StepFault(
                f"injected train-step fault at step "
                f"{int(np.asarray(self._step_count))}")
        if tel is not None:
            from ..analysis.recompile_guard import compile_count

            c0 = compile_count()
        self.params, self.opt_state, self._step_count, loss = self._train_step(
            self.params, self.opt_state, self._step_count, lr, batch_vals)
        if tel is not None:
            t_dispatch = tel.clock()
            jax.block_until_ready(loss)
            t_wait = tel.clock()
            if not tel.model_params:
                tel.model_params = sum(
                    int(np.prod(v.shape)) for n, v in self.params.items()
                    if n in self._trainable)
            first = batch_vals[0] if batch_vals else None
            tokens = 0 if first is None else \
                int(np.prod(first.shape[:2])) if first.ndim >= 2 \
                else int(first.shape[0])
            prog = "train:" + ";".join(
                "x".join(map(str, b.shape)) for b in batch_vals)
            tel.record_step(
                step=int(np.asarray(self._step_count)) - 1, prog=prog,
                tokens=tokens, t0=t0, t_h2d=t_h2d, t_dispatch=t_dispatch,
                t_wait=t_wait, compiles=compile_count() - c0)
        from ..framework.monitor import monitor_add

        monitor_add("engine_train_steps")
        from ..distributed.fleet.elastic import pulse_heartbeat

        pulse_heartbeat()  # progress-based hang detection (--elastic_timeout)
        if isinstance(self.optimizer._learning_rate, object) and hasattr(
                self.optimizer._learning_rate, "step"):
            try:
                self.optimizer._learning_rate.step()
            except TypeError:
                pass
        return Tensor(loss)

    def eval_batch(self, *batch):
        if self._eval_step is None:
            def ev(params, batch):
                return self._loss_from_batch(params, batch)

            self._eval_step = jax.jit(ev)
        return Tensor(self._eval_step(self.params,
                                      self._assemble_batch(batch)))

    # ------------------------------------------------------------------- sync
    def engine_state_dict(self):
        """Host snapshot of the FULL engine training state (params +
        optimizer moments + step counter) for checkpoint/resume across
        elastic restarts (ref auto_checkpoint.py exactly-once resume; the
        reference snapshots executor scope vars, here the donated jit
        state). Values come back as numpy; sharded arrays are gathered —
        multi-process callers need replicated or addressable state (DP/
        ZeRO-replicated layouts qualify; every rank then writes an
        identical snapshot, so rank-local files are interchangeable)."""
        return {
            "params": jax.tree.map(np.asarray, dict(self.params)),
            "opt_state": jax.tree.map(np.asarray, self.opt_state),
            "step": int(np.asarray(self._step_count)),
        }

    def set_engine_state(self, state):
        """Inverse of engine_state_dict: re-place host values against this
        engine's shardings (works across process/mesh layouts as long as
        shapes match — the reshard is the placement)."""
        mesh = self.mesh
        if self._spmd:
            self.params = {
                n: self._place(v, _sharding_of(mesh, self.specs.get(n, P())))
                for n, v in state["params"].items()}
            self.opt_state = {
                n: {k: self._place(v, _sharding_of(mesh, self.specs.get(n, P())))
                    for k, v in slots.items()}
                for n, slots in state["opt_state"].items()}
        else:
            self.params = {n: jnp.asarray(v)
                           for n, v in state["params"].items()}
            if self._offload_opt and self.opt_state:
                host = self._host_sharding()
                self.opt_state = jax.tree.map(
                    lambda v: jax.device_put(v, host), state["opt_state"])
            else:
                self.opt_state = jax.tree.map(jnp.asarray,
                                              state["opt_state"])
        step = state.get("step", 0)
        self._step_count = (np.asarray(step, np.int32)
                            if jax.process_count() > 1
                            else jnp.asarray(step, jnp.int32))

    def sync_to_model(self):
        store = {**dict(self.model.named_parameters()),
                 **dict(self.model.named_buffers())}
        for name, v in self.params.items():
            if name in store:
                store[name]._value = v

    def state_dict(self):
        self.sync_to_model()
        return self.model.state_dict()


def parallelize(model, optimizer=None, loss_fn=None, mesh=None, **kwargs) -> ParallelEngine:
    return ParallelEngine(model, optimizer=optimizer, loss_fn=loss_fn, mesh=mesh, **kwargs)


def make_train_step(model, loss_fn, optimizer, mesh=None, **kwargs):
    eng = ParallelEngine(model, optimizer=optimizer, loss_fn=loss_fn, mesh=mesh, **kwargs)
    eng.build_train_step()
    return eng
