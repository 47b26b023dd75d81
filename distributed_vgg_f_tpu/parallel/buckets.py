"""Bucketed, overlap-capable gradient exchange (ISSUE 11 / ROADMAP item 5).

The step used to run compute-then-exchange: the whole backward pass finished
before a single monolithic collective moved every gradient byte — one
`pmean` per LEAF in plain DP (dozens of small collectives, all emitted after
the full backward in trace order) and, worse, ONE flat `psum_scatter` of the
entire padded parameter vector under ZeRO sharding: a collective whose
operand depends on every backward op, i.e. a pure serial tail at pod scale.

This module is the classic fix (communication scheduling — arXiv 1711.00705,
arXiv 1603.02339): partition the parameter pytree into size-targeted
BUCKETS ordered by reverse-backward position (the last layers' gradients are
ready first, so bucket 0 can hit the wire while the convs are still
back-propagating) and issue each bucket's collective independently:

  - plain DP: one `pmean` per bucket (groups the per-leaf all-reduces into
    ICI-friendly message sizes without serializing them behind the full
    backward);
  - ZeRO-1/2: one `psum_scatter` per bucket — each bucket's gradients are
    reduce-scattered to their 1/N shard AS SOON AS THEY EXIST, so the
    full-size flat send buffer of the monolithic path never materializes
    and XLA's latency-hiding scheduler can run bucket k's collective under
    the backward compute that feeds bucket k+1.

The overlap claim is STRUCTURAL, not aspirational, and `hlo_overlap_report`
is the committed assertion: it parses a lowered step and proves that (a)
the exchange lowered to >= 2 gradient-sized collectives and (b) there
exists a (collective, backward-matmul/conv) pair with NO dependency path in
either direction — exactly the property a latency-hiding scheduler needs to
run them concurrently. The monolithic scatter fails (b) by construction
(every backward op is its ancestor).

ZeRO shard layout under bucketing
---------------------------------
Scattering per bucket changes which elements each replica owns: replica r
holds piece r OF EACH BUCKET, not the r-th contiguous slice of the
canonical (tree_leaves-order) flat vector. The persistent flat layout is
therefore **bucket-major, replica-interleaved**:

    global[(r * S) + off_b : (r * S) + off_b + s_b] = bucket_b[r*s_b : (r+1)*s_b]

with S = sum(s_b) the per-replica shard length and off_b the running shard
offset of bucket b. `to_global`/`from_global` are the exact (static, pure)
permutations between this layout and the params tree, so checkpoint
migration to/from the ZeRO-1 canonical flat layout goes through
`parallel.zero.convert_opt_state` losslessly (checkpoint/retopology.py
reads the geometry receipt the trainer stores in the checkpoint's `extra`).
`comm_bucket_mb` unset keeps the canonical single-flat layout and the
pre-r14 step byte-for-byte (the kill-switch lowered-text identity is
pinned in tests/test_comm_buckets.py).
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_vgg_f_tpu.parallel.collectives import (
    cast_from_wire,
    cast_to_wire,
)

#: Gradient bytes per element used for bucket sizing — gradients are fp32 in
#: train/step.py regardless of compute dtype (the wire may narrow them, but
#: bucket GEOMETRY must not depend on mesh.reduce_dtype or flipping the wire
#: would silently re-layout a ZeRO checkpoint).
GRAD_BYTES_PER_ELEM = 4


@dataclasses.dataclass(frozen=True)
class GradBucketLayout:
    """Static bucket geometry for one (params tree, shard count, target).

    `buckets` holds canonical `jax.tree.leaves` indices in EMISSION order:
    bucket 0 contains the LAST leaves of the tree (reverse-backward
    position — their gradients exist first). All methods are pure jnp and
    traceable; geometry is decided here, once, from shapes alone, so the
    scan carry, the scatter padding, the param-shard slicing, the opt-state
    length, and the checkpoint receipt can never disagree.
    """

    num_shards: int
    bucket_bytes: int                       # configured target (> 0)
    treedef: Any                            # canonical params treedef
    leaf_shapes: Tuple[Tuple[int, ...], ...]
    leaf_dtypes: Tuple[Any, ...]
    buckets: Tuple[Tuple[int, ...], ...]    # per bucket: canonical leaf idx

    # ------------------------------------------------------------ geometry
    @property
    def num_buckets(self) -> int:
        return len(self.buckets)

    def _leaf_size(self, idx: int) -> int:
        # math.prod(()) == 1 covers scalars; a genuinely zero-element leaf
        # must count 0 or the bucket offsets drift off the real ravel
        return int(math.prod(self.leaf_shapes[idx]))

    def bucket_sizes(self) -> Tuple[int, ...]:
        """Unpadded element count per bucket."""
        return tuple(sum(self._leaf_size(i) for i in b)
                     for b in self.buckets)

    def padded_sizes(self) -> Tuple[int, ...]:
        """Per-bucket length after padding to a multiple of num_shards."""
        return tuple(n + (-n) % self.num_shards for n in self.bucket_sizes())

    def shard_sizes(self) -> Tuple[int, ...]:
        return tuple(p // self.num_shards for p in self.padded_sizes())

    @property
    def shard_size(self) -> int:
        """Per-replica flat shard length S = sum(s_b)."""
        return sum(self.shard_sizes())

    @property
    def total_padded(self) -> int:
        """Global flat opt-state length T = N * S = sum(p_b)."""
        return sum(self.padded_sizes())

    def describe(self) -> dict:
        """The checkpoint/JSONL geometry receipt. Everything needed to
        rebuild the layout (`build_bucket_layout` is deterministic in
        (leaf shapes, num_shards, bucket_bytes)) plus `total_padded` as the
        integrity check a restore verifies before trusting the rebuild."""
        return {"kind": "bucketed_flat",
                "num_shards": self.num_shards,
                "bucket_bytes": self.bucket_bytes,
                "num_buckets": self.num_buckets,
                "total_padded": self.total_padded,
                "bucket_elems": list(self.bucket_sizes())}

    # ----------------------------------------------------- tree <-> buckets
    def _bucket_vector(self, leaves: Sequence[Any], b: int,
                       pad: bool) -> jnp.ndarray:
        parts = [jnp.ravel(leaves[i]) for i in self.buckets[b]]
        vec = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        if pad:
            p = self.padded_sizes()[b]
            if p != vec.shape[0]:
                vec = jnp.pad(vec, (0, p - vec.shape[0]))
        return vec

    def _leaves_from_bucket_vectors(self, vecs: Sequence[Any]) -> List[Any]:
        """Inverse of per-bucket ravel: padded (or unpadded) bucket vectors
        back to canonical-order leaves (C-order reshape — the exact layout
        `jnp.ravel` produced)."""
        out: List[Any] = [None] * len(self.leaf_shapes)
        for b, vec in enumerate(vecs):
            off = 0
            for i in self.buckets[b]:
                n = self._leaf_size(i)
                out[i] = jnp.reshape(vec[off:off + n],
                                     self.leaf_shapes[i]).astype(
                                         self.leaf_dtypes[i])
                off += n
        return out

    def unflatten(self, leaves: Sequence[Any]) -> Any:
        return jax.tree.unflatten(self.treedef, list(leaves))

    # -------------------------------------------------------- the DP leg
    def pmean_buckets(self, grads: Any, axis_name: str,
                      wire_dtype=None) -> Any:
        """Per-bucket mean-all-reduce of a gradient pytree: each bucket's
        leaves ride ONE collective (cast to the wire dtype through the same
        single-sourced helper as every other leg). Elementwise identical to
        the per-leaf pmean it groups — concatenation permutes nothing
        within an element — so the loss trajectory is unchanged."""
        leaves = jax.tree.leaves(grads)
        out_vecs = []
        for b in range(self.num_buckets):
            vec = self._bucket_vector(leaves, b, pad=False)
            wire = cast_to_wire(vec, wire_dtype)
            out_vecs.append(cast_from_wire(
                lax.pmean(wire, axis_name=axis_name), vec.dtype))
        return self.unflatten(self._leaves_from_bucket_vectors(out_vecs))

    # ------------------------------------------------------ the ZeRO legs
    def scatter_mean_shards(self, grads: Any, axis_name: str,
                            wire_dtype=None) -> jnp.ndarray:
        """Per-bucket [SYNC] reduce-scatter of a gradient pytree to this
        replica's fp32 mean flat shard (length S, bucket-major). Each
        bucket's collective depends only on ITS leaves' gradients — the
        overlap-capable emission. The wire may narrow per bucket
        (mesh.reduce_dtype through the single-sourced cast); the mean and
        everything downstream are fp32."""
        leaves = jax.tree.leaves(grads)
        shards = []
        for b in range(self.num_buckets):
            send = cast_to_wire(self._bucket_vector(leaves, b, pad=True),
                                wire_dtype)
            piece = lax.psum_scatter(send, axis_name, scatter_dimension=0,
                                     tiled=True)
            shards.append(cast_from_wire(piece, jnp.float32)
                          / self.num_shards)
        return shards[0] if len(shards) == 1 else jnp.concatenate(shards)

    def local_param_shard(self, params: Any, axis_name: str) -> jnp.ndarray:
        """This replica's (S,) slice of the bucket-major flat params —
        the piece the sharded optimizer updates."""
        r = lax.axis_index(axis_name)
        leaves = jax.tree.leaves(params)
        pieces = []
        for b, s_b in enumerate(self.shard_sizes()):
            vec = self._bucket_vector(leaves, b, pad=True)
            pieces.append(lax.dynamic_slice_in_dim(
                vec.astype(jnp.float32), r * s_b, s_b))
        return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)

    def gather_params(self, param_shard: jnp.ndarray,
                      axis_name: str) -> Any:
        """[SYNC] all-gather of the updated (S,) shards back to the full
        params tree — replicas re-sync exactly (always fp32; the gather leg
        is never narrowed, config.py mesh.reduce_dtype contract)."""
        full = lax.all_gather(param_shard, axis_name, tiled=True)
        return self.from_global(full)

    def gather_param_tree(self, param_shard: jnp.ndarray, axis_name: str,
                          wire_dtype=None) -> Any:
        """ZeRO-3 [SYNC] just-in-time gather: ONE `all_gather` PER BUCKET
        of this replica's (s_b,) piece back to that bucket's full leaves —
        each collective's operand is a static slice of the (S,) param
        shard (a step INPUT, no compute ancestry at all), so every gather
        carries the structural license a latency-hiding scheduler needs
        to pipeline it under the forward compute of earlier-consumed
        buckets (`hlo_overlap_report` gather witness). Unlike the ZeRO-1/2
        re-sync gather above, the wire may narrow (mesh.reduce_dtype
        through the SAME single-sourced cast as every scatter leg): the
        gathered replica is a transient of this one step, not persistent
        state — the fp32 truth lives in the shard. wire_dtype=None keeps
        the gather exact (bit-identical to the ZeRO-2 params)."""
        vecs, off = [], 0
        for b, s_b in enumerate(self.shard_sizes()):
            piece = cast_to_wire(
                lax.slice_in_dim(param_shard, off, off + s_b), wire_dtype)
            full = lax.all_gather(piece, axis_name, tiled=True)
            vecs.append(cast_from_wire(full, jnp.float32))
            off += s_b
        return self.unflatten(self._leaves_from_bucket_vectors(vecs))

    # --------------------------------------- global flat layout (opt state)
    def to_global(self, params: Any) -> jnp.ndarray:
        """Params tree -> the (T,) bucket-major replica-interleaved global
        flat vector (the ZeRO-2 opt-state/checkpoint layout; row r of the
        (N, S) view is replica r's shard)."""
        leaves = jax.tree.leaves(params)
        rows = [jnp.reshape(
            self._bucket_vector(leaves, b, pad=True).astype(jnp.float32),
            (self.num_shards, s_b))
            for b, s_b in enumerate(self.shard_sizes())]
        mat = rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)
        return jnp.reshape(mat, (self.total_padded,))

    def from_global(self, vec: jnp.ndarray) -> Any:
        """Inverse of `to_global`: (T,) global flat vector (or the tiled
        all_gather of per-replica shards — the same layout) -> params
        tree. Pure static slicing; padding elements are dropped."""
        mat = jnp.reshape(vec, (self.num_shards, self.shard_size))
        vecs, off = [], 0
        for b, s_b in enumerate(self.shard_sizes()):
            vecs.append(jnp.reshape(mat[:, off:off + s_b],
                                    (self.padded_sizes()[b],)))
            off += s_b
        return self.unflatten(self._leaves_from_bucket_vectors(vecs))


def sharding_basis(zero1: bool, shard_gradients: bool,
                   shard_params: bool = False) -> str:
    """THE (dp | zero1 | zero2 | zero3) basis derivation — the single
    source for the exchange plan's EFFECTIVE basis (`zero.plan_exchange`,
    after its single-shard downgrade; the step's comm_meta receipt reports
    it) and config.MeshConfig's CONFIGURED label. The ladder is cumulative:
    zero3 implies zero2 implies zero1 (config validation enforces it;
    callers pass the post-downgrade flags)."""
    if zero1 and shard_gradients and shard_params:
        return "zero3"
    if zero1 and shard_gradients:
        return "zero2"
    return "zero1" if zero1 else "dp"


def exchange_wire_bytes(n_elem: int, padded_total: int, *, zero: bool,
                        wire_dtype=None,
                        shard_params: bool = False) -> Dict[str, int]:
    """Logical collective payload bytes per step per replica (algorithm
    bytes — the ring factor 2(N-1)/N lives in utils/scaling_model.py).
    DP: one all-reduce of the gradient bytes on the (possibly narrowed)
    wire. ZeRO-1/2: scatter leg on the wire dtype + fp32 param gather leg
    (the post-update re-sync — replicas must agree bit-exactly, so the
    gather never narrows). ZeRO-3 (`shard_params`): the SAME two legs,
    but the gather is the just-in-time pre-forward param fetch and rides
    the wire dtype (the gathered replica is a step transient, not
    persistent state) — under a narrowed wire ZeRO-3 is the only basis
    whose BOTH legs shrink. One accounting for both layouts
    (`zero.Exchange.comm_meta`)."""
    wire_itemsize = (jnp.dtype(wire_dtype).itemsize
                     if wire_dtype is not None else 4)
    if not zero:
        b = n_elem * wire_itemsize
        return {"allreduce_bytes": b, "scatter_bytes": 0,
                "gather_bytes": 0, "wire_bytes": b}
    scatter = padded_total * wire_itemsize
    gather = padded_total * (wire_itemsize if shard_params else 4)
    return {"allreduce_bytes": 0, "scatter_bytes": scatter,
            "gather_bytes": gather, "wire_bytes": scatter + gather}


def build_bucket_layout(params: Any, num_shards: int,
                        bucket_bytes: int) -> Optional[GradBucketLayout]:
    """Partition a params pytree (concrete arrays or ShapeDtypeStructs)
    into size-targeted buckets in reverse-backward order. `bucket_bytes`
    <= 0 returns None — the single-flat kill-switch (callers keep the
    exact pre-r14 code path). Leaves are atomic (the PyTorch-DDP
    convention): a leaf larger than the target becomes its own bucket, so
    the target is a GRANULARITY floor, not a hard cap — VGG's FC layers
    each ride one bucket, the conv tail groups into few."""
    if bucket_bytes <= 0:
        return None
    leaves, treedef = jax.tree.flatten(params)
    if not leaves:
        raise ValueError("cannot bucket an empty params tree")
    shapes = tuple(tuple(getattr(l, "shape", ())) for l in leaves)
    dtypes = tuple(jnp.dtype(getattr(l, "dtype", jnp.float32))
                   for l in leaves)
    buckets: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_bytes = 0
    # reverse-backward emission: the LAST leaves' gradients exist first
    for idx in reversed(range(len(leaves))):
        nbytes = int(math.prod(shapes[idx])) * GRAD_BYTES_PER_ELEM
        if cur and cur_bytes + nbytes > bucket_bytes:
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(idx)
        cur_bytes += nbytes
    if cur:
        buckets.append(tuple(cur))
    return GradBucketLayout(num_shards=int(num_shards),
                            bucket_bytes=int(bucket_bytes),
                            treedef=treedef, leaf_shapes=shapes,
                            leaf_dtypes=dtypes, buckets=tuple(buckets))


def layout_from_receipt(params: Any, receipt: dict) -> GradBucketLayout:
    """Rebuild a layout from a checkpoint geometry receipt (`describe()`),
    verifying the reconstruction against EVERY recorded geometry field —
    total_padded, bucket count, AND the per-bucket element sizes (two
    partitions can share a padded total while permuting differently, e.g.
    two layers trading widths). A model/geometry mismatch must fail
    loudly, never silently permute a momentum vector — and it fails as
    the TYPED `GeometryReceiptError` (r19, resilience/errors.py): wrong
    layout, not corrupt bytes, so elastic restore and the flight recorder
    can tell the two apart (the class subclasses ValueError, so pre-r19
    catch sites are unchanged)."""
    from distributed_vgg_f_tpu.resilience.errors import GeometryReceiptError
    if receipt.get("kind") != "bucketed_flat":
        raise GeometryReceiptError(
            f"unknown opt-layout kind {receipt.get('kind')!r}")
    layout = build_bucket_layout(params, int(receipt["num_shards"]),
                                 int(receipt["bucket_bytes"]))
    rebuilt = None if layout is None else {
        "total_padded": layout.total_padded,
        "num_buckets": layout.num_buckets,
        "bucket_elems": list(layout.bucket_sizes())}
    recorded = {"total_padded": int(receipt["total_padded"]),
                "num_buckets": int(receipt["num_buckets"]),
                "bucket_elems": [int(n) for n in receipt["bucket_elems"]]}
    if rebuilt != recorded:
        raise GeometryReceiptError(
            f"bucket-layout receipt does not reproduce on this params "
            f"tree: rebuilt {rebuilt} != recorded {recorded} — the "
            f"checkpoint was written for a different model or geometry")
    return layout


# ---------------------------------------------------------------------------
# Lowered-HLO overlap evidence (the committed assertion, not a prose claim)
# ---------------------------------------------------------------------------

#: StableHLO collective op names that move gradient/param payloads.
COLLECTIVE_OPS = ("all_reduce", "reduce_scatter", "all_gather",
                  "all_to_all", "collective_permute")
#: The backward/forward compute ops a collective must be able to run under.
COMPUTE_OPS = ("dot_general", "convolution")

_INSTR_RE = re.compile(r"^\s*(%[\w]+)(?::\d+)?\s*=\s*(.*)$")
_OP_RE = re.compile(r"stablehlo\.([a-z_0-9]+)")
_REF_RE = re.compile(r"%([\w]+)(?:#\d+)?")
_TYPE_RE = re.compile(r"tensor<([^>]*)>")


def _tensor_elems(type_str: str) -> int:
    dims = []
    for tok in type_str.split("x"):
        if tok.isdigit():
            dims.append(int(tok))
        else:
            break                    # element type reached (f32, ui8, ...)
    return int(math.prod(dims)) if dims else 1


def _parse_functions(text: str) -> List[List[dict]]:
    """Split a StableHLO module into functions and parse each function's
    TOP-LEVEL instructions: {id, op, operands, elems}. Region bodies
    (all_reduce summation lambdas etc.) are skipped — their SSA numbers are
    function-local re-uses; the result type of a region-bearing op is read
    off its `}) : ...` closing line."""
    funcs: List[List[dict]] = []
    cur: Optional[List[dict]] = None
    depth = 0
    pending: Optional[dict] = None
    for line in text.splitlines():
        if line.lstrip().startswith("func.func"):
            cur = []
            funcs.append(cur)
            depth = 0
            pending = None
            continue
        if cur is None:
            continue
        opens = line.count("({")
        closes = line.count("})")
        if depth == 0:
            m = _INSTR_RE.match(line)
            if m:
                body = m.group(2)
                opm = _OP_RE.search(body)
                refs = [r for r in _REF_RE.findall(body)
                        if not r.startswith("arg")]
                types = _TYPE_RE.findall(line)
                instr = {"id": m.group(1).lstrip("%"),
                         "op": opm.group(1) if opm else "",
                         "operands": refs,
                         "elems": _tensor_elems(types[-1]) if types else 0}
                cur.append(instr)
                if opens > closes:
                    pending = instr        # type arrives on the `})` line
        elif depth + opens - closes == 0 and pending is not None:
            types = _TYPE_RE.findall(line)
            if types:
                pending["elems"] = _tensor_elems(types[-1])
            pending = None
        depth += opens - closes
    return funcs


def _ancestors(instrs: List[dict]) -> Dict[str, set]:
    # One forward pass in textual order: StableHLO is SSA, so every
    # operand's definition precedes its use and each instruction's
    # ancestor set is already complete when reached. Iterative on purpose
    # — a large model's longest dependency chain (resnet50 lowers to
    # thousands of chained instructions) overflows Python's recursion
    # limit under the equivalent memoized DFS.
    by_id = {i["id"]: i for i in instrs}
    memo: Dict[str, set] = {}
    for i in instrs:
        acc: set = set()
        for ref in i.get("operands", ()):
            if ref in by_id:
                acc.add(ref)
                acc |= memo.get(ref, set())
        memo[i["id"]] = acc
    return memo


def hlo_overlap_report(text: str, *, min_elems: int = 64) -> dict:
    """Analyze a lowered train step's StableHLO text for the committed
    overlap properties. Returns

      {collective_counts: {op: n}, grad_collectives: n,
       overlap_capable: bool, witness: {...} | None,
       serial_tail_collectives: n, compute_ops: n,
       gathers: n, gather_overlap_capable: bool,
       gather_witness: {...} | None}

    `grad_collectives` counts collectives whose payload carries at least
    `min_elems` elements (the metrics pmean moves scalars; gradient buckets
    move thousands). `overlap_capable` is true iff some gradient collective
    C and some dot_general/convolution D have NO dependency path in either
    direction — the structural license for a latency-hiding scheduler to
    overlap them. A monolithic flat scatter can never satisfy it: every
    compute op feeds it. `serial_tail_collectives` counts gradient
    collectives whose ancestor set contains EVERY compute op (the
    fully-serialized ones this PR exists to break up).

    r21 (ZeRO-3): `gathers` counts the gradient-sized `all_gather`
    collectives (the just-in-time param fetch — one per bucket under the
    bucketed ZeRO-3 exchange; the single re-sync gather under ZeRO-1/2),
    and `gather_witness`/`gather_overlap_capable` apply the SAME
    dependency-free-pair test restricted to the gather ops: a param
    gather that neither feeds nor is fed by some dot/conv is one a
    latency-hiding scheduler may run under the forward compute of
    already-gathered buckets.

    Scope: analyzes TOP-LEVEL instructions per function — collectives
    inside control-flow regions (the grad-accum scan's `stablehlo.while`
    body) are deliberately out of scope, so run the overlap assertions on
    a grad_accum_steps=1 lowering (the bench and tier-1 tests do)."""
    best: Optional[dict] = None
    for instrs in _parse_functions(text):
        colls = [i for i in instrs if i["op"] in COLLECTIVE_OPS]
        if not colls:
            continue
        anc = _ancestors(instrs)
        computes = [i for i in instrs if i["op"] in COMPUTE_OPS]
        compute_ids = {i["id"] for i in computes}
        grad_colls = [c for c in colls if c["elems"] >= min_elems]
        witness = None
        gather_witness = None
        serial_tail = 0
        for c in grad_colls:
            c_anc = anc.get(c["id"], set())
            if compute_ids and compute_ids <= c_anc:
                serial_tail += 1
            if witness is None or (c["op"] == "all_gather"
                                   and gather_witness is None):
                for d in computes:
                    if d["id"] not in c_anc \
                            and c["id"] not in anc.get(d["id"], set()):
                        pair = {
                            "collective": f"%{c['id']} = {c['op']} "
                                          f"({c['elems']} elems)",
                            "compute": f"%{d['id']} = {d['op']}"}
                        if witness is None:
                            witness = pair
                        if c["op"] == "all_gather" \
                                and gather_witness is None:
                            gather_witness = pair
                        break
        counts: Dict[str, int] = {}
        for c in colls:
            counts[c["op"]] = counts.get(c["op"], 0) + 1
        report = {"collective_counts": counts,
                  "grad_collectives": len(grad_colls),
                  "overlap_capable": witness is not None,
                  "witness": witness,
                  "serial_tail_collectives": serial_tail,
                  "compute_ops": len(computes),
                  # over ALL collectives, not just gradient-sized ones: the
                  # param gathers are the only all_gather ops a step emits
                  # (metrics ride all_reduce), and a tiny trailing bucket's
                  # gather must still count toward `gathers == buckets`
                  "gathers": sum(1 for c in colls
                                 if c["op"] == "all_gather"),
                  "gather_overlap_capable": gather_witness is not None,
                  "gather_witness": gather_witness}
        if best is None or report["grad_collectives"] \
                > best["grad_collectives"]:
            best = report
    return best or {"collective_counts": {}, "grad_collectives": 0,
                    "overlap_capable": False, "witness": None,
                    "serial_tail_collectives": 0, "compute_ops": 0,
                    "gathers": 0, "gather_overlap_capable": False,
                    "gather_witness": None}
