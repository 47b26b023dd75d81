"""Cross-topology checkpoint restore (BASELINE north_star: train on v4-8,
grow to v4-128 — and migrate replicated DP ↔ ZeRO-1 — without retraining).

A checkpoint's optimizer-state layout is a function of HOW it was trained:
replicated DP saves a params-tree optax state; ZeRO-1 saves one flat vector
padded to a multiple of the shard count (parallel/zero.py), so its shapes
change with the mesh size. Restoring onto a different topology must therefore
ADAPT the state, not just reshard it.

Strategy:
1. Detect the saved layout from checkpoint metadata (shapes only, no array
   reads — checkpoint/manager.py `state_metadata`).
2. Fast path: saved shapes == template shapes → plain Orbax restore (Orbax
   reshards to the template's shardings natively; this covers N→M meshes
   whose padded sizes happen to coincide, and all replicated-DP resizes).
3. Otherwise restore at the SAVED shapes (opt state replicated), then convert
   with `parallel.zero.convert_opt_state` inside one jitted computation whose
   `out_shardings` are the target layout — XLA places the result directly
   into the target topology, on one host or many.

Step/batch_stats are topology-independent (always replicated over the data
axis) and restore bit-identically on any mesh. Params (and EMA params) were
too — until ZeRO-3 (r21, mesh.shard_params), which persists them as the SAME
padded flat vector the opt state uses. They now flow through the identical
detect → receipt-check → restore-replicated → jitted-convert machinery
(`parallel.zero.convert_params`), keyed by the `param_layout` receipt in the
checkpoint's `extra` (kind: canonical_flat | bucketed_flat; absent receipt on
a flat vector = canonical — and on a tree = the pre-r21 layout). Any
direction works: zero2 ↔ zero3, N ↔ M shards, bucketed ↔ canonical — or
refuses with a typed GeometryReceiptError, never a shape error.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax

from distributed_vgg_f_tpu.parallel.zero import (
    Exchange,
    convert_opt_state,
    convert_params,
    flat_param_count,
    layout_from_receipt,
    opt_state_layout,
    params_layout,
)
from distributed_vgg_f_tpu.resilience.errors import GeometryReceiptError


def restore_any_topology(manager, template, target: Exchange, *,
                         step: Optional[int] = None) -> tuple:
    """Restore `manager`'s checkpoint into `template`'s topology and layout.

    - `template`: concrete TrainState initialized for the CURRENT run: its
      shardings define the target topology.
    - `target`: the current run's exchange plan (parallel/zero.py): the
      layout the template's opt state (and, under ZeRO-3, its params and
      EMA) is in, the params TREE geometry, the optimizer. The saved side's
      geometry comes from the receipts the trainer writes into every
      checkpoint's `extra` (`Exchange.receipts`): `opt_layout` for a
      bucket-major flat vector (absent = the canonical layout, true for
      every pre-r14 checkpoint), `param_layout` for flat params. Saved
      state in ANY layout — replicated tree, canonical flat, bucket-major
      flat, any shard count — is converted to the template's, with typed
      refusals.

    Returns `(state, extra)` like `manager.restore`.
    """
    step = step if step is not None else manager.best_step()
    # a dp plan meets the parameter shapes here at the latest
    target = target.bind(template.params)
    params_struct = target.params_struct
    saved_meta = manager.state_metadata(step)
    saved_opt_meta = saved_meta["opt_state"]
    saved_shapes = [tuple(l.shape) for l in jax.tree.leaves(saved_opt_meta)]
    tmpl_shapes = [tuple(l.shape) for l in jax.tree.leaves(template.opt_state)]
    total = flat_param_count(params_struct)
    layout, padded_src = opt_state_layout(saved_opt_meta, total)
    # The saved FLAT layout's geometry receipt: same-shape vectors can
    # still be differently PERMUTED (canonical vs bucket-major, or two
    # bucket sizes whose totals coincide) — shapes alone cannot
    # disambiguate, the receipt can.
    saved_layout_receipt = None
    if layout == "flat":
        saved_layout_receipt = (manager.extra_at(step) or {}).get(
            "opt_layout")
        if saved_layout_receipt is not None:
            try:
                layout_from_receipt(params_struct, saved_layout_receipt)
            except ValueError as e:
                # r19: a receipt that names a non-reproducing geometry is
                # WRONG LAYOUT, not corrupt bytes — the typed class lets
                # elastic restore tell the flight recorder which one it
                # was (corrupt bytes raise CheckpointIntegrityError long
                # before this point, in the manager's manifest check)
                raise GeometryReceiptError(
                    f"opt-layout receipt at step {step} does not describe "
                    f"this run's geometry: {e}") from e
    target_receipts = target.receipts()
    target_layout_receipt = target_receipts.get("opt_layout")

    # -- params side (r21): detect the SAVED params layout (replicated tree
    # vs ZeRO-3 flat) and the template's, plus the `param_layout` receipt
    # that disambiguates canonical vs bucket-major flat (same shapes,
    # different permutation — exactly the opt-state ambiguity).
    saved_p_meta = saved_meta["params"]
    saved_p_shapes = [tuple(l.shape) for l in jax.tree.leaves(saved_p_meta)]
    tmpl_p_shapes = [tuple(l.shape)
                     for l in jax.tree.leaves(template.params)]
    s_p_layout, s_p_padded = params_layout(saved_p_meta, total)
    saved_param_receipt = None
    param_source = None
    if s_p_layout == "flat":
        saved_param_receipt = (manager.extra_at(step) or {}).get(
            "param_layout")
        kind = (saved_param_receipt or {}).get("kind", "canonical_flat")
        if saved_param_receipt is not None \
                and saved_param_receipt.get("total_padded") != s_p_padded:
            raise GeometryReceiptError(
                f"param-layout receipt at step {step} claims total_padded="
                f"{saved_param_receipt.get('total_padded')} but the saved "
                f"flat params vector has length {s_p_padded}")
        if kind == "bucketed_flat":
            # a bucketed flat params vector always rides with the bucketed
            # opt vector — ONE layout, described once by the opt receipt
            if saved_layout_receipt is None:
                raise GeometryReceiptError(
                    f"param-layout receipt at step {step} says "
                    f"'bucketed_flat' but no opt-layout receipt describes "
                    f"the bucket geometry — cannot invert the permutation")
            param_source = saved_layout_receipt
    elif (manager.extra_at(step) or {}).get("param_layout") is not None:
        raise GeometryReceiptError(
            f"param-layout receipt present at step {step} but the saved "
            f"params are a tree, not a flat vector — receipt and payload "
            f"disagree")
    # comparison keys: (kind, padded) per side, where an ABSENT receipt on
    # a flat vector means the canonical layout (pre-receipt writers) — so
    # absence and an explicit canonical receipt of the same length compare
    # equal. Bucketed-flat interleaving additionally depends on the bucket
    # geometry, which the opt receipts carry.
    saved_p_key = target_p_key = None
    if s_p_layout == "flat":
        saved_p_key = ((saved_param_receipt or {}).get(
            "kind", "canonical_flat"), s_p_padded)
    if target.zero3:
        target_p_key = (target_receipts["param_layout"]["kind"],
                        target.total_padded)
    params_match = (saved_p_shapes == tmpl_p_shapes
                    and saved_p_key == target_p_key
                    and (saved_layout_receipt == target_layout_receipt
                         or (saved_p_key or ("",))[0] != "bucketed_flat"))

    if saved_shapes == tmpl_shapes \
            and saved_layout_receipt == target_layout_receipt \
            and params_match:
        return manager.restore(template, step)

    # -- layout mismatch: rebuild the SAVED opt-state structure abstractly
    if layout == "flat":
        src_struct = jax.eval_shape(
            target.tx.init,
            jax.ShapeDtypeStruct((padded_src,), jax.numpy.float32))
    else:
        src_struct = jax.eval_shape(target.tx.init, params_struct)
    src_shapes = [tuple(l.shape) for l in jax.tree.leaves(src_struct)]
    if src_shapes != saved_shapes:
        raise ValueError(
            f"checkpoint opt-state shapes {saved_shapes} match neither the "
            f"current topology {tmpl_shapes} nor a reconstruction of the "
            f"saved layout {src_shapes} — was it written by a different "
            f"optimizer chain?")

    # restore at the saved shapes, replicated over the current mesh
    replicated = template.step.sharding
    saved_template = template.replace(opt_state=jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=replicated),
        src_struct))
    if not params_match:
        # rebuild the SAVED params structure abstractly, replicated — the
        # flat vector (any shard count) or the plain tree
        if s_p_layout == "flat":
            src_p_struct = jax.ShapeDtypeStruct(
                (s_p_padded,), jax.numpy.float32, sharding=replicated)
        else:
            src_p_struct = jax.tree.map(
                lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=replicated),
                params_struct)
        src_p_shapes = [tuple(l.shape)
                        for l in jax.tree.leaves(src_p_struct)]
        if src_p_shapes != saved_p_shapes:
            raise GeometryReceiptError(
                f"checkpoint params shapes {saved_p_shapes} match neither "
                f"the current topology {tmpl_p_shapes} nor a reconstruction "
                f"of the saved layout {src_p_shapes} — was it written for a "
                f"different model?")
        saved_template = saved_template.replace(
            params=src_p_struct,
            ema_params=(src_p_struct if template.ema_params is not None
                        else template.ema_params))
    restored, extra = manager.restore(saved_template, step)

    # convert the layout inside jit: out_shardings (the template's own)
    # place the result straight into the target topology
    shardings_of = lambda tree: jax.tree.map(lambda l: l.sharding, tree)
    convert = jax.jit(
        functools.partial(convert_opt_state, source=saved_layout_receipt,
                          target=target),
        out_shardings=shardings_of(template.opt_state))
    out = restored.replace(opt_state=convert(restored.opt_state))
    if not params_match:
        conv_p = jax.jit(
            functools.partial(convert_params, source=param_source,
                              target=target),
            out_shardings=shardings_of(template.params))
        new_ema = (conv_p(restored.ema_params)
                   if template.ema_params is not None else restored.ema_params)
        out = out.replace(params=conv_p(restored.params), ema_params=new_ema)
    return out, extra
