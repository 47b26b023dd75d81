"""ctypes bindings for the native libjpeg loader (native/jpeg_loader.cc —
DCT-scaled partial decode + crop + resize + normalize in C++ worker threads).

This is the framework's own native decode path (SURVEY.md §2.2 native layer;
README measures the tf.data host path as the end-to-end bottleneck). Items are
byte ranges, so the same decoder serves both ImageNet layouts: whole .JPEG
files (raw directory-per-class) and JPEG values inside TFRecord shards
(ranges emitted by data/native_tfrecord.py). Built on demand with g++ -ljpeg;
all callers must tolerate `load_native_jpeg() is None` and fall back to the
tf.data pipeline — the native loader is a throughput optimization, not a
correctness dependency.

The resample half of the decode runs through runtime-dispatched SIMD kernels
(AVX2+FMA with a byte-identical scalar fallback — jpeg_loader.cc "resample
kernels"): `simd_kind()` reports the active path, `set_simd()` forces it
(parity tests, before/after benches), `decode_profile()` exposes the
libjpeg-vs-resample phase split, and DVGGF_DECODE_SIMD=0 is the env
kill-switch.

The libjpeg half (r7) dispatches the same way: `scaled_kind()` /
`set_scaled()` control the DCT-scaled + partial decode strategy
(DVGGF_DECODE_SCALED=0 is its env kill-switch, -DDVGGF_NO_SCALED the
compile-out), `partial_supported()` reports whether the running
libjpeg-turbo resolves the crop/skip-scanline partial-decode API (dlsym
probe — plain libjpeg gets the full-decode fallback), `choose_scale()`
exposes the native scale chooser (`expected_scale_denom` is its pure-Python
mirror, pinned equal by the tests), and `decode_stats()` returns the decode
receipts: chosen-scale histogram, scanlines skipped/truncated around the
crop window, and the per-thread decode-buffer-pool hit rate.

The wire half (r8): `image_dtype='uint8'` selects the uint8 wire — raw
resampled HWC pixels through fixed-point integer kernels (normalize, dtype
cast and space-to-depth move to the device-finish prologue,
data/device_ingest.py), shrinking the output ring 4x vs f32.
`wire_u8_supported()` / `wire_u8_enabled()` / `set_wire_u8()` mirror the
PR 2/3 dispatch surface; DVGGF_WIRE_U8=0 is the env kill-switch and
-DDVGGF_NO_WIRE_U8 the compile-out — with the wire refused, loader creation
with the u8 kind FAILS and data/imagenet.py falls back to the
host-normalize wire (byte-identical to the r7 behavior).

The entropy half (r9): `restart_kind()` / `set_restart()` control the
restart-marker excerpt decode — when a stream carries usable RSTn structure
(DRI interval dividing or divisible by the MCU row), the decoder
entropy-parses ONLY the segments covering the crop band instead of every
row above it, byte-identically to the sequential path
(DVGGF_DECODE_RESTART=0 is the env kill-switch, -DDVGGF_NO_RESTART the
compile-out). `restart_fanout()` / `set_restart_fanout()` split one image's
band across the native chunk pool (latency lever; default 1),
`restart_stats()` returns the engagement receipts, and
`reencode_restart()` losslessly injects markers into plain JPEGs (the
offline dataset tool's engine, benchmarks/reencode_restart.py).

The pool half (r11): `set_num_threads()` / `num_threads()` grow or shrink a
LIVE loader's decode worker pool (ABI v8) — the ingest autotuner's
decode-worker knob (data/autotune.py). `thread_resize_supported()` /
`thread_resize_enabled()` / `set_thread_resize()` mirror the dispatch
surface; DVGGF_THREAD_RESIZE=0 is the env kill-switch and
-DDVGGF_NO_RESIZE the compile-out (resize then refuses; the stream itself
is identical at any width, so the switch guards who may actuate, not what
is decoded).

The flip half (r13, ABI v9): per-loader flip ownership — construct the
train iterator with `hflip=False` when the fused on-device augmentation
stage (data/augment.py, `data.augment.hflip`) owns the horizontal flip, and
the host decode never flips (exactly one side holds the flag, so
double-flip is structurally impossible). `decode_single_image` takes the
same `hflip` switch for the snapshot cache's repair path. The per-item flip
bit is drawn from the RNG either way, so crop geometry — and every later
item in the stream — is bit-identical at both settings.

Determinism contract (train): the batch stream is a pure function of (seed,
batch index) — same seed, same stream, regardless of thread count — and
`restore_state(step)` is an O(1) exact seek (no snapshot files), satisfying
the trainer's deterministic-resume protocol (SURVEY.md §5).

Eval (`NativeJpegEvalIterator`): deterministic center crop (the original-
coordinate preimage of resize-short-side-256 → center-crop), one in-order
finite pass; the final partial batch arrives zero-padded with a `valid` count
for the exact-eval pad-and-mask protocol (data/eval_pad.py).
"""

from __future__ import annotations

import ctypes
import logging
import os
import threading
from typing import Optional, Sequence

import numpy as np


log = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False

_I64P = ctypes.POINTER(ctypes.c_int64)
_I32P = ctypes.POINTER(ctypes.c_int32)
_F32P = ctypes.POINTER(ctypes.c_float)

#: Must match dvgg_jpeg_loader_abi_version() in native/jpeg_loader.cc —
#: single source for the load gate and the build smoke test.
JPEG_ABI_VERSION = 9

#: out_kind values of the v6 ABI (the loaders' former bf16_out int; 0/1
#: keep their meaning). 2 = the uint8 wire: raw resampled HWC pixels —
#: normalize/cast/space-to-depth move to the device-finish prologue
#: (data/device_ingest.py).
_OUT_KINDS = {"float32": 0, "bfloat16": 1, "uint8": 2}


def load_native_jpeg() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        from distributed_vgg_f_tpu.data.native_build import load_abi_checked
        lib = load_abi_checked("jpeg_loader.cc", "libdvgg_jpeg.so",
                               "dvgg_jpeg_loader_abi_version",
                               JPEG_ABI_VERSION,
                               extra_link_args=("-ljpeg", "-ldl"))
        if lib is None:
            _build_failed = True
            return None
        lib.dvgg_jpeg_loader_create.restype = ctypes.c_void_p
        lib.dvgg_jpeg_loader_create.argtypes = [
            ctypes.c_char_p, _I64P, _I32P, ctypes.c_int64, ctypes.c_int,
            ctypes.c_int, ctypes.c_uint64, _F32P, _F32P, ctypes.c_int,
            ctypes.c_int, ctypes.c_double, ctypes.c_double]
        lib.dvgg_jpeg_loader_create_ranged.restype = ctypes.c_void_p
        lib.dvgg_jpeg_loader_create_ranged.argtypes = [
            ctypes.c_char_p, _I64P, ctypes.c_int64, _I32P, _I64P, _I64P,
            _I32P, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
            ctypes.c_uint64, _F32P, _F32P, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_double, ctypes.c_int, ctypes.c_int,
            ctypes.c_int]
        lib.dvgg_jpeg_loader_next.restype = ctypes.c_int
        lib.dvgg_jpeg_loader_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, _I32P]
        lib.dvgg_jpeg_loader_next_valid.restype = ctypes.c_int
        lib.dvgg_jpeg_loader_next_valid.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, _I32P, _I32P]
        lib.dvgg_jpeg_loader_seek.restype = None
        lib.dvgg_jpeg_loader_seek.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.dvgg_jpeg_loader_decode_errors.restype = ctypes.c_int64
        lib.dvgg_jpeg_loader_decode_errors.argtypes = [ctypes.c_void_p]
        lib.dvgg_jpeg_loader_destroy.restype = None
        lib.dvgg_jpeg_loader_destroy.argtypes = [ctypes.c_void_p]
        lib.dvgg_jpeg_decode_single.restype = ctypes.c_int
        lib.dvgg_jpeg_decode_single.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, _F32P, _F32P,
            ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
            ctypes.c_double, ctypes.c_double, ctypes.c_uint64,
            ctypes.c_void_p]
        lib.dvgg_jpeg_simd_supported.restype = ctypes.c_int
        lib.dvgg_jpeg_simd_supported.argtypes = []
        lib.dvgg_jpeg_simd_kind.restype = ctypes.c_int
        lib.dvgg_jpeg_simd_kind.argtypes = []
        lib.dvgg_jpeg_set_simd.restype = ctypes.c_int
        lib.dvgg_jpeg_set_simd.argtypes = [ctypes.c_int]
        lib.dvgg_jpeg_profile_ns.restype = None
        lib.dvgg_jpeg_profile_ns.argtypes = [_I64P]
        lib.dvgg_jpeg_profile_reset.restype = None
        lib.dvgg_jpeg_profile_reset.argtypes = []
        lib.dvgg_jpeg_scaled_supported.restype = ctypes.c_int
        lib.dvgg_jpeg_scaled_supported.argtypes = []
        lib.dvgg_jpeg_scaled_kind.restype = ctypes.c_int
        lib.dvgg_jpeg_scaled_kind.argtypes = []
        lib.dvgg_jpeg_set_scaled.restype = ctypes.c_int
        lib.dvgg_jpeg_set_scaled.argtypes = [ctypes.c_int]
        lib.dvgg_jpeg_partial_supported.restype = ctypes.c_int
        lib.dvgg_jpeg_partial_supported.argtypes = []
        lib.dvgg_jpeg_choose_scale.restype = ctypes.c_int
        lib.dvgg_jpeg_choose_scale.argtypes = [ctypes.c_int, ctypes.c_int,
                                               ctypes.c_int]
        lib.dvgg_jpeg_decode_stats.restype = None
        lib.dvgg_jpeg_decode_stats.argtypes = [_I64P]
        lib.dvgg_jpeg_decode_stats_reset.restype = None
        lib.dvgg_jpeg_decode_stats_reset.argtypes = []
        lib.dvgg_jpeg_wire_u8_supported.restype = ctypes.c_int
        lib.dvgg_jpeg_wire_u8_supported.argtypes = []
        lib.dvgg_jpeg_wire_u8_kind.restype = ctypes.c_int
        lib.dvgg_jpeg_wire_u8_kind.argtypes = []
        lib.dvgg_jpeg_set_wire_u8.restype = ctypes.c_int
        lib.dvgg_jpeg_set_wire_u8.argtypes = [ctypes.c_int]
        lib.dvgg_jpeg_restart_supported.restype = ctypes.c_int
        lib.dvgg_jpeg_restart_supported.argtypes = []
        lib.dvgg_jpeg_restart_kind.restype = ctypes.c_int
        lib.dvgg_jpeg_restart_kind.argtypes = []
        lib.dvgg_jpeg_set_restart.restype = ctypes.c_int
        lib.dvgg_jpeg_set_restart.argtypes = [ctypes.c_int]
        lib.dvgg_jpeg_restart_fanout.restype = ctypes.c_int
        lib.dvgg_jpeg_restart_fanout.argtypes = []
        lib.dvgg_jpeg_set_restart_fanout.restype = ctypes.c_int
        lib.dvgg_jpeg_set_restart_fanout.argtypes = [ctypes.c_int]
        lib.dvgg_jpeg_restart_stats.restype = None
        lib.dvgg_jpeg_restart_stats.argtypes = [_I64P]
        lib.dvgg_jpeg_restart_stats_reset.restype = None
        lib.dvgg_jpeg_restart_stats_reset.argtypes = []
        lib.dvgg_jpeg_reencode_restart.restype = ctypes.c_int64
        lib.dvgg_jpeg_reencode_restart.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_int, ctypes.c_void_p,
            ctypes.c_int64]
        lib.dvgg_jpeg_resize_supported.restype = ctypes.c_int
        lib.dvgg_jpeg_resize_supported.argtypes = []
        lib.dvgg_jpeg_resize_kind.restype = ctypes.c_int
        lib.dvgg_jpeg_resize_kind.argtypes = []
        lib.dvgg_jpeg_set_resize.restype = ctypes.c_int
        lib.dvgg_jpeg_set_resize.argtypes = [ctypes.c_int]
        lib.dvgg_jpeg_loader_set_threads.restype = ctypes.c_int
        lib.dvgg_jpeg_loader_set_threads.argtypes = [ctypes.c_void_p,
                                                     ctypes.c_int]
        lib.dvgg_jpeg_loader_num_threads.restype = ctypes.c_int
        lib.dvgg_jpeg_loader_num_threads.argtypes = [ctypes.c_void_p]
        lib.dvgg_jpeg_loader_set_hflip.restype = ctypes.c_int
        lib.dvgg_jpeg_loader_set_hflip.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_int]
        lib.dvgg_jpeg_loader_hflip.restype = ctypes.c_int
        lib.dvgg_jpeg_loader_hflip.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


_SIMD_KINDS = {0: "scalar", 1: "avx2"}


def simd_kind() -> Optional[str]:
    """Resample path the native decoder is currently dispatching to
    ('scalar' | 'avx2'), or None when the library is unavailable. The
    initial value honors cpuid and the DVGGF_DECODE_SIMD=0 kill-switch."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return _SIMD_KINDS.get(int(lib.dvgg_jpeg_simd_kind()), "unknown")


def set_simd(enabled: bool) -> Optional[str]:
    """Force the resample path at runtime (False → scalar; True → SIMD when
    the CPU supports it). Returns the now-active kind — how the parity tests
    and the decode bench run both paths in one process."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return _SIMD_KINDS.get(int(lib.dvgg_jpeg_set_simd(int(enabled))),
                           "unknown")


_SCALED_KINDS = {0: "full", 1: "scaled"}

#: The power-of-two scale_num candidates the native chooser draws from.
#: libjpeg-turbo carries SIMD IDCT kernels ONLY for these output sizes
#: (8x8 / 4x4 / 2x2; 1x1 is DC-only) — a 5/8..7/8 decode runs a slower
#: plain-C IDCT and measured net-SLOWER than full 8/8 on the same crop.
SCALE_CANDIDATES = (1, 2, 4, 8)


def expected_scale_denom(crop_w: int, crop_h: int, out_size: int) -> int:
    """Pure-Python mirror of the native scale chooser (jpeg_loader.cc
    choose_scale_m, exported as dvgg_jpeg_choose_scale): the smallest M in
    SCALE_CANDIDATES whose M/8-scaled crop still covers `out_size` in both
    dims (floor semantics), else 8 — so the resample NEVER upscales pixels
    that a smaller DCT scale would have thrown away. The tests pin this
    mirror equal to the native ABI's reported choice across source sizes
    and crop modes; drift between the two is a chooser bug."""
    for m in SCALE_CANDIDATES:
        if (crop_w * m) // 8 >= out_size and (crop_h * m) // 8 >= out_size:
            return m
    return 8


def scaled_supported() -> Optional[bool]:
    """Whether the DCT-scaled + partial decode machinery was compiled in
    (False on a -DDVGGF_NO_SCALED build), or None when the library is
    unavailable."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_scaled_supported())


def scaled_kind() -> Optional[str]:
    """Decode strategy the native decoder is currently dispatching to
    ('full' | 'scaled'), or None when the library is unavailable. The
    initial value honors the DVGGF_DECODE_SCALED=0 kill-switch."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return _SCALED_KINDS.get(int(lib.dvgg_jpeg_scaled_kind()), "unknown")


def set_scaled(enabled: bool) -> Optional[str]:
    """Force the decode strategy at runtime (False → full-resolution
    decode; True → DCT-scaled + partial when compiled in). Returns the
    now-active kind — how the tolerance-parity suite and the decode bench
    run both strategies in one process."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return _SCALED_KINDS.get(int(lib.dvgg_jpeg_set_scaled(int(enabled))),
                             "unknown")


def partial_supported() -> Optional[bool]:
    """Whether the running libjpeg resolves the turbo-only partial-decode
    API (jpeg_crop_scanline + jpeg_skip_scanlines, dlsym-probed). False
    means the scaled path decodes full-width rows and discards — same
    pixels, more IDCT. None when the library is unavailable."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_partial_supported())


def wire_u8_supported() -> Optional[bool]:
    """Whether the uint8 wire mode was compiled in (False on a
    -DDVGGF_NO_WIRE_U8 build), or None when the library is unavailable."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_wire_u8_supported())


def wire_u8_enabled() -> bool:
    """True iff a uint8-wire loader can be created RIGHT NOW: library
    loaded, wire compiled in, and neither the DVGGF_WIRE_U8=0 env
    kill-switch nor set_wire_u8(False) has refused it. The ingest layer
    (data/imagenet.py) checks this BEFORE requesting image_dtype='uint8' —
    when False it falls back to the host-normalize wire, byte-identical to
    the pre-u8 (r7) behavior."""
    lib = load_native_jpeg()
    if lib is None:
        return False
    return bool(lib.dvgg_jpeg_wire_u8_kind())


def set_wire_u8(enabled: bool) -> Optional[bool]:
    """Force the u8-wire availability at runtime (False → loader creation
    with the u8 kind refuses; True → available when compiled in). Returns
    the now-active availability — how the fallback tests exercise both
    wires in one process. Only affects loaders created after the call."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_set_wire_u8(int(enabled)))


_RESTART_KINDS = {0: "sequential", 1: "restart"}


def restart_supported() -> Optional[bool]:
    """Whether the restart-marker excerpt decode (r9) was compiled in
    (False on a -DDVGGF_NO_RESTART build), or None when the library is
    unavailable."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_restart_supported())


def restart_kind() -> Optional[str]:
    """Entropy-decode strategy the native decoder is currently dispatching
    to ('sequential' | 'restart'), or None when the library is unavailable.
    The initial value honors the DVGGF_DECODE_RESTART=0 kill-switch.
    'restart' engages per image, only when the stream carries usable RSTn
    structure — sources without markers ride the sequential path either way
    (receipted in restart_stats()['marker_absent'])."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return _RESTART_KINDS.get(int(lib.dvgg_jpeg_restart_kind()), "unknown")


def set_restart(enabled: bool) -> Optional[str]:
    """Force the entropy strategy at runtime (False → sequential; True →
    restart excerpts when compiled in). Returns the now-active kind — how
    the parity suite decodes the same marker-bearing bytes through both
    entropy paths in one process. Byte-identical either way, by contract."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return _RESTART_KINDS.get(int(lib.dvgg_jpeg_set_restart(int(enabled))),
                              "unknown")


def restart_fanout() -> Optional[int]:
    """Active intra-image fan-out width (1 = no fan-out). The initial value
    honors the DVGGF_RESTART_FANOUT env default."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return int(lib.dvgg_jpeg_restart_fanout())


def set_restart_fanout(width: int) -> Optional[int]:
    """Set how many entropy chunks one image's crop band may be split into
    and decoded concurrently (clamped to [1, 64]). Returns the now-active
    width. Fan-out trades cores for LATENCY (decode_single, predict
    ingest); per-core throughput — the provisioning metric — is served by
    width 1, the default."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return int(lib.dvgg_jpeg_set_restart_fanout(int(width)))


#: Field order of dvgg_jpeg_restart_stats (single source for the wrapper
#: and its tests).
_RESTART_STAT_FIELDS = (
    "images", "marker_absent", "unsupported", "misaligned", "scan_failures",
    "excerpt_fallbacks", "segments_used", "segments_skipped",
    "fanout_images", "fanout_width_max", "chunk_jobs_pooled", "no_gain")


def restart_stats(reset: bool = False) -> Optional[dict]:
    """Cumulative restart-path receipts since load (or the last reset),
    process-wide: images decoded via excerpts, the fallback causes split
    by reason (marker_absent / unsupported / misaligned / scan_failures /
    excerpt_fallbacks), entropy segments decoded vs never parsed (the
    skipped Huffman work — the whole point), fan-out accounting, and
    no_gain (the band needed every segment, so sequential was used). A
    dataset that never engages the path is diagnosable from this receipt
    alone."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    buf = (ctypes.c_int64 * 16)()
    lib.dvgg_jpeg_restart_stats(buf)
    if reset:
        lib.dvgg_jpeg_restart_stats_reset()
    return {k: int(buf[i]) for i, k in enumerate(_RESTART_STAT_FIELDS)}


def thread_resize_supported() -> Optional[bool]:
    """Whether runtime thread-pool grow/shrink (r11, ABI v8) was compiled
    in (False on a -DDVGGF_NO_RESIZE build), or None when the library is
    unavailable."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_resize_supported())


def thread_resize_enabled() -> bool:
    """True iff a live loader's worker pool can be resized RIGHT NOW:
    library loaded, resize compiled in, and neither the
    DVGGF_THREAD_RESIZE=0 env kill-switch nor set_thread_resize(False) has
    refused it. The ingest autotuner (data/autotune.py) checks this before
    binding its decode-worker knob — a refused resize means the knob is
    simply absent, never a silent no-op."""
    lib = load_native_jpeg()
    if lib is None:
        return False
    return bool(lib.dvgg_jpeg_resize_kind())


def set_thread_resize(enabled: bool) -> Optional[bool]:
    """Force the resize availability at runtime (False → set_num_threads
    refuses; True → allowed when compiled in). Returns the now-active
    availability — how the kill-switch tests exercise both behaviors in
    one process."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return bool(lib.dvgg_jpeg_set_resize(int(enabled)))


def reencode_restart(data: bytes, interval_mcus: int = 0) -> Optional[bytes]:
    """Losslessly transcode one JPEG so its entropy stream carries restart
    markers every `interval_mcus` MCUs (0 = one marker per MCU row — the
    row-trimmable layout the excerpt decoder engages on). Coefficient-
    domain copy (jpeg_read/write_coefficients, the jpegtran move): decoded
    pixels are bit-identical to the source's; progressive sources
    additionally normalize to baseline sequential. Returns the transcoded
    bytes, or None when the source doesn't decode (corrupt/unsupported).
    Raises when the native library itself is unavailable. This is the
    engine of the offline dataset tool (benchmarks/reencode_restart.py)."""
    lib = load_native_jpeg()
    if lib is None:
        raise RuntimeError("native jpeg loader unavailable")
    data = bytes(data)
    cap = len(data) + len(data) // 2 + 65536
    for _ in range(2):
        buf = ctypes.create_string_buffer(cap)
        rc = int(lib.dvgg_jpeg_reencode_restart(data, len(data),
                                                int(interval_mcus), buf, cap))
        if rc > 0:
            return buf.raw[:rc]
        if rc == -1:
            return None
        if rc == -2:
            raise ValueError("bad reencode_restart arguments")
        cap = -rc  # buffer too small: the return names the needed size
    raise RuntimeError("reencode_restart did not converge on a buffer size")


def choose_scale(crop_w: int, crop_h: int, out_size: int) -> Optional[int]:
    """The native ABI's scale chooser (scale_num over a fixed denom of 8)
    for a (crop_w, crop_h) source region resized to out_size — the value
    `expected_scale_denom` mirrors. None when the library is unavailable."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    return int(lib.dvgg_jpeg_choose_scale(int(crop_w), int(crop_h),
                                          int(out_size)))


def decode_stats(reset: bool = False) -> Optional[dict]:
    """Cumulative decode receipts since load (or the last reset),
    process-wide across all worker threads: images decoded, the
    chosen-scale histogram {scale_num: count}, scanlines skipped above /
    truncated below the crop window, decode-buffer-pool hits/misses (and
    the derived hit rate), images decoded through the partial crop+skip
    path, and full-decode fallbacks (scaled wanted, turbo API absent).
    The decode bench embeds this as the 'what did the decoder actually
    do' receipt next to the phase profile."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    buf = (ctypes.c_int64 * 16)()
    lib.dvgg_jpeg_decode_stats(buf)
    if reset:
        lib.dvgg_jpeg_decode_stats_reset()
    hits, misses = int(buf[11]), int(buf[12])
    return {
        "images": int(buf[0]),
        "scale_histogram": {m: int(buf[m]) for m in range(1, 9)
                            if int(buf[m])},
        "rows_skipped": int(buf[9]),
        "rows_truncated": int(buf[10]),
        "pool_hits": hits,
        "pool_misses": misses,
        "pool_hit_rate": (hits / (hits + misses)
                          if hits + misses else None),
        "partial_images": int(buf[13]),
        "full_fallbacks": int(buf[14]),
    }


def decode_profile(reset: bool = False) -> Optional[dict]:
    """Cumulative successful-decode phase split since load (or the last
    reset): {'jpeg_s', 'resample_s', 'images'} — libjpeg entropy+IDCT time
    vs the resample kernels, process-wide across all worker threads. The
    committed-profile source for 'where does the remaining decode time go'
    (benchmarks/host_pipeline_bench.py --decode-bench)."""
    lib = load_native_jpeg()
    if lib is None:
        return None
    buf = (ctypes.c_int64 * 3)()
    lib.dvgg_jpeg_profile_ns(buf)
    if reset:
        lib.dvgg_jpeg_profile_reset()
    return {"jpeg_s": buf[0] / 1e9, "resample_s": buf[1] / 1e9,
            "images": int(buf[2])}


def register_decode_poller() -> None:
    """Fold the native decoder's process-wide receipts into the telemetry
    registry under the `decode/` namespace (cumulative, so per-window
    deltas work): images, scale histogram, skipped/truncated scanlines,
    pool hits/misses, partial/fallback counts, and the libjpeg-vs-resample
    phase seconds. Called by the iterator constructors AFTER the library is
    known to be loaded — the telemetry package itself never imports this
    module, so `import distributed_vgg_f_tpu.telemetry` can never trigger a
    native build (the import-isolation contract). Idempotence is keyed on
    the REGISTRY's state (has_poller), not a module flag: telemetry.reset()
    drops pollers, and a module flag would sever decode counters for every
    iterator constructed after a reset (code-review r8)."""
    from distributed_vgg_f_tpu import telemetry
    if telemetry.get_registry().has_poller("decode"):
        return

    def _poll():
        st = decode_stats()
        if st is None:
            return None
        out = {k: st[k] for k in
               ("images", "rows_skipped", "rows_truncated", "pool_hits",
                "pool_misses", "partial_images", "full_fallbacks")}
        out["scale_histogram"] = st["scale_histogram"]
        prof = decode_profile()
        if prof is not None:
            out["jpeg_s"] = prof["jpeg_s"]
            out["resample_s"] = prof["resample_s"]
        rst = restart_stats()
        if rst is not None:  # r9: the entropy-path receipts ride along
            for k, v in rst.items():
                out[f"restart_{k}"] = v
        return out

    telemetry.register_poller("decode", _poll, cumulative=True)


def decode_single_image(data: bytes, out_size: int, mean, std, *,
                        image_dtype: str = "float32", pack4: bool = False,
                        eval_mode: bool = False, area_range=(0.08, 1.0),
                        rng_seed: int = 0, hflip: bool = True, out=None):
    """Stateless one-image decode through the SAME native crop/resize/
    normalize math as the batch loader (native/jpeg_loader.cc
    dvgg_jpeg_decode_single). Returns the decoded array, or None on decode
    failure (corrupt/unsupported JPEG — callers zero-fill). Raises when the
    native library itself is unavailable. The parity suite drives both
    resample paths through this.

    `hflip=False` (ABI v9) reproduces the crop from a flips-disabled
    stream — the fused on-device augmentation stage (data/augment.py) owns
    the flip then, and the snapshot cache's repair path must match the
    unflipped capture. The flip bit is drawn either way, so the crop
    geometry is identical at both settings.

    `out` (r16): decode straight into a caller-owned C-contiguous array of
    the right shape/dtype — the disaggregated-ingest worker assembles
    batches item-by-item, and a per-item temp + copy is ~10%% of its
    produce budget at batch 64. Returns `out` on success."""
    lib = load_native_jpeg()
    if lib is None:
        raise RuntimeError("native jpeg loader unavailable")
    if pack4 and out_size % 4 != 0:
        raise ValueError("pack4 needs out_size % 4 == 0")
    if image_dtype not in _OUT_KINDS:
        raise ValueError(
            f"image_dtype {image_dtype!r} not one of {sorted(_OUT_KINDS)}")
    if image_dtype == "uint8" and pack4:
        raise ValueError("the uint8 wire never packs on the host — "
                         "space-to-depth belongs to the device-finish "
                         "prologue (data/device_ingest.py)")
    bf16 = image_dtype == "bfloat16"
    if bf16:
        import ml_dtypes
        raw_dtype, np_dtype = np.uint16, np.dtype(ml_dtypes.bfloat16)
    elif image_dtype == "uint8":
        raw_dtype, np_dtype = np.uint8, np.dtype(np.uint8)
    else:
        raw_dtype, np_dtype = np.float32, np.dtype(np.float32)
    if pack4:
        shape = (out_size // 4, out_size // 4, 48)
    else:
        shape = (out_size, out_size, 3)
    mean = np.ascontiguousarray(mean, np.float32)
    std = np.ascontiguousarray(std, np.float32)
    if out is None:
        out = np.empty(shape, raw_dtype)
    else:
        if tuple(out.shape) != shape:
            raise ValueError(f"out shape {out.shape} != {shape}")
        if bf16:
            # only a 2-byte-element buffer may alias the bf16 output: a
            # wider dtype would pass .view() after a reshape and end up
            # silently half-filled with bf16 bit patterns
            if out.dtype.itemsize != 2:
                raise ValueError(
                    f"out dtype {out.dtype} is not 2-byte (bfloat16/"
                    f"uint16) for the bfloat16 wire")
            out = out.view(np.uint16)
        elif out.dtype != raw_dtype:
            raise ValueError(f"out dtype {out.dtype} != {raw_dtype}")
        if not out.flags.c_contiguous:
            raise ValueError("out must be C-contiguous")
    rc = lib.dvgg_jpeg_decode_single(
        bytes(data), len(data), int(out_size),
        mean.ctypes.data_as(_F32P), std.ctypes.data_as(_F32P),
        _OUT_KINDS[image_dtype], int(pack4), int(eval_mode), int(hflip),
        float(area_range[0]), float(area_range[1]), int(rng_seed),
        out.ctypes.data_as(ctypes.c_void_p))
    if rc == 1:
        return None
    if rc != 0:
        if image_dtype == "uint8" and not wire_u8_enabled():
            raise RuntimeError(
                "uint8 wire refused by the native library (compiled out or "
                "kill-switched) — use the host-normalize wire")
        raise RuntimeError(f"dvgg_jpeg_decode_single rc={rc}")
    return out.view(np_dtype) if bf16 else out


def _paths_blob(files: Sequence[str]):
    blob = b"".join(p.encode() for p in files)
    offsets = np.zeros(len(files) + 1, np.int64)
    np.cumsum([len(p.encode()) for p in files], out=offsets[1:])
    return blob, offsets


def _whole_file_ranges(n: int):
    """(path_idx, offsets, lengths) for n whole-file items — one path per
    item, offset<0 meaning "the entire file" (the raw-JPEG layout)."""
    return (np.arange(n, dtype=np.int32), np.full(n, -1, np.int64),
            np.zeros(n, np.int64))


class _NativeJpegBase:
    """Shared handle/buffer plumbing for the train and eval iterators.

    Handles are EXPLICIT: `_create_ranged` returns one and tracks it in
    `_live`; `_next_raw`/`_destroy` take it as an argument. The eval iterator
    gives each pass (each `iter()`) its own handle, so interleaved or
    abandoned generators can never consume or destroy each other's stream.

    Buffer ownership: by default every batch is a FRESH numpy array the
    caller owns outright — safe for any consumer, including device_put
    paths that may alias host memory. `enable_output_buffer_reuse(depth)`
    switches to a ring of `depth` preallocated output arrays (a large-batch
    array is multi-MB; allocating + page-faulting one per batch costs real
    per-image time): a yielded batch is then only valid until `depth` more
    `next()` calls, which is why `maybe_prefetch` REFUSES such an iterator
    (data/prefetch.py — the device-prefetch thread hands batches to an
    async device_put whose lifetime the ring cannot see). Bench-only.
    """

    #: Batches the native workers may have decoded beyond the last one
    #: handed out (native/jpeg_loader.cc `kDepth`: an item is claimed while
    #: its batch index is less than `kDepth` past the consumer's) — so the
    #: counters (`decode_errors`) run ahead of the consumer by up to this.
    decode_ahead_batches = 3

    def __init__(self, lib, batch: int, image_size: int, image_dtype: str):
        self._lib = lib
        self.batch = int(batch)
        self.image_size = int(image_size)
        if image_dtype not in _OUT_KINDS:
            raise ValueError(
                f"image_dtype {image_dtype!r} not one of {sorted(_OUT_KINDS)}")
        self._out_kind = _OUT_KINDS[image_dtype]
        self._bf16 = image_dtype == "bfloat16"
        if self._bf16:
            import ml_dtypes
            self._np_dtype = np.dtype(ml_dtypes.bfloat16)
            self._raw_dtype = np.uint16
        elif image_dtype == "uint8":
            # the u8 wire: raw resampled pixels — consumers MUST run the
            # device-finish prologue (data/device_ingest.py) exactly once
            self._np_dtype = np.dtype(np.uint8)
            self._raw_dtype = np.uint8
        else:
            self._np_dtype = np.dtype(np.float32)
            self._raw_dtype = np.float32
        #: public receipt of the dtype this iterator actually ships — the
        #: bench reads it to refuse printing a u8-labeled row for a loader
        #: that silently fell back to a host-normalize kind
        self.image_dtype = image_dtype
        self._live: list = []            # open native handles
        self._decode_errors_closed = 0   # latched counts of destroyed handles
        # per-item output shape; the packed train iterator overrides this
        self._out_shape = (self.image_size, self.image_size, 3)
        self._buf_ring: list = []        # output-array ring (opt-in)
        self._buf_i = 0

    @property
    def reuses_output_buffers(self) -> bool:
        """True once `enable_output_buffer_reuse` armed the ring — consumers
        that keep batch references alive (device prefetch) must check this
        and refuse."""
        return bool(self._buf_ring)

    def enable_output_buffer_reuse(self, depth: int = 3) -> None:
        """Arm a ring of `depth` preallocated (batch, ...) output arrays —
        each `next()` then recycles the oldest instead of allocating. The
        returned batch is only valid until `depth` further `next()` calls:
        strictly for benchmarking loops that consume batches synchronously
        (benchmarks/host_pipeline_bench.py --decode-bench)."""
        if depth < 2:
            raise ValueError(f"ring depth must be >= 2, got {depth}")
        self._buf_ring = [
            (np.empty((self.batch,) + self._out_shape, self._raw_dtype),
             np.empty((self.batch,), np.int32))
            for _ in range(depth)]
        self._buf_i = 0

    def _create_ranged(self, files, path_idx, offsets, lengths, labels, *,
                       seed, mean, std, num_threads, area_range, eval_mode,
                       finite, pack4=False):
        lib = self._lib
        blob, path_offsets = _paths_blob(files)
        path_idx = np.ascontiguousarray(path_idx, np.int32)
        offsets = np.ascontiguousarray(offsets, np.int64)
        lengths = np.ascontiguousarray(lengths, np.int64)
        labels = np.ascontiguousarray(labels, np.int32)
        mean = np.ascontiguousarray(mean, np.float32)
        std = np.ascontiguousarray(std, np.float32)
        if num_threads is None:
            num_threads = max(1, min(8, (os.cpu_count() or 1)))
        handle = lib.dvgg_jpeg_loader_create_ranged(
            blob, path_offsets.ctypes.data_as(_I64P), len(files),
            path_idx.ctypes.data_as(_I32P), offsets.ctypes.data_as(_I64P),
            lengths.ctypes.data_as(_I64P), labels.ctypes.data_as(_I32P),
            len(labels), self.batch, self.image_size, seed,
            mean.ctypes.data_as(_F32P), std.ctypes.data_as(_F32P),
            num_threads, self._out_kind,
            float(area_range[0]), float(area_range[1]),
            int(eval_mode), int(finite), int(pack4))
        if not handle:
            if self._out_kind == _OUT_KINDS["uint8"] and not wire_u8_enabled():
                raise RuntimeError(
                    "uint8 wire refused by the native library (compiled out "
                    "with -DDVGGF_NO_WIRE_U8, or killed via DVGGF_WIRE_U8=0 "
                    "/ set_wire_u8(False)) — use the host-normalize wire")
            raise RuntimeError("dvgg_jpeg_loader_create_ranged failed")
        self._live.append(handle)
        return handle

    def _next_raw(self, handle):
        """(images, labels, valid) for the next batch; None at end-of-stream."""
        if self._buf_ring:
            raw, labels = self._buf_ring[self._buf_i % len(self._buf_ring)]
            self._buf_i += 1
        else:
            raw = np.empty((self.batch,) + self._out_shape, self._raw_dtype)
            labels = np.empty((self.batch,), np.int32)
        valid = ctypes.c_int32(self.batch)
        rc = self._lib.dvgg_jpeg_loader_next_valid(
            handle, raw.ctypes.data_as(ctypes.c_void_p),
            labels.ctypes.data_as(_I32P), ctypes.byref(valid))
        if rc == 1:
            return None
        if rc != 0:
            raise RuntimeError(f"dvgg_jpeg_loader_next rc={rc}")
        images = raw.view(self._np_dtype) if self._bf16 else raw
        return images, labels, int(valid.value)

    def _destroy(self, handle) -> None:
        if handle in self._live:
            self._decode_errors_closed += int(
                self._lib.dvgg_jpeg_loader_decode_errors(handle))
            self._lib.dvgg_jpeg_loader_destroy(handle)
            self._live.remove(handle)

    def decode_errors(self) -> int:
        """Cumulative corrupt-image count across this iterator's lifetime
        (live handles + already-closed passes)."""
        live = sum(int(self._lib.dvgg_jpeg_loader_decode_errors(h))
                   for h in self._live)
        return self._decode_errors_closed + live

    def set_num_threads(self, n: int) -> Optional[int]:
        """Runtime-resize the native decode worker pool (r11, ABI v8) —
        the ingest autotuner's decode-worker knob. Grow spawns workers into
        the live item-claim loop; shrink retires idle workers before their
        next item claim. The batch stream is BYTE-IDENTICAL at any width
        (pure function of (seed, batch index)), so this is an operational
        knob, not a format one. Returns the now-active target, or None when
        refused (no live handle, -DDVGGF_NO_RESIZE build, or the
        DVGGF_THREAD_RESIZE=0 / set_thread_resize(False) kill-switch) —
        callers must treat None as 'knob unavailable'."""
        if not self._live:
            return None
        rc = -1
        for handle in self._live:
            rc = int(self._lib.dvgg_jpeg_loader_set_threads(handle, int(n)))
        return None if rc < 0 else rc

    def num_threads(self) -> Optional[int]:
        """Current worker-count target (creation value until the first
        resize), or None with no live handle."""
        if not self._live:
            return None
        rc = int(self._lib.dvgg_jpeg_loader_num_threads(self._live[-1]))
        return None if rc < 0 else rc

    def close(self) -> None:
        for handle in list(getattr(self, "_live", [])):
            self._destroy(handle)

    def __del__(self):  # pragma: no cover — best-effort cleanup
        try:
            self.close()
        except Exception:
            pass


class NativeJpegTrainIterator(_NativeJpegBase):
    """Infinite deterministic train iterator over JPEG items.

    Items are either whole files (`files` + `labels`) or byte ranges into
    container files (`ranges=(path_idx, offsets, lengths)` — the TFRecord
    layout via data/native_tfrecord.py). Yields {'image': (B, S, S, 3)
    float32|bfloat16, 'label': (B,) int32}. `restore_state(step)` seeks to
    "next batch = step" in O(1).
    """

    supports_state = True

    def __init__(self, files: Sequence[str], labels: Sequence[int],
                 batch: int, image_size: int, *, seed: int,
                 mean: np.ndarray, std: np.ndarray,
                 image_dtype: str = "float32",
                 num_threads: int | None = None,
                 area_range=(0.08, 1.0),
                 ranges=None,
                 space_to_depth: bool = False,
                 hflip: bool = True):
        lib = load_native_jpeg()
        if lib is None:
            raise RuntimeError("native jpeg loader unavailable")
        if not len(files):
            raise ValueError("empty file list")
        if space_to_depth and image_size % 4 != 0:
            raise ValueError("space_to_depth needs image_size % 4 == 0")
        if space_to_depth and image_dtype == "uint8":
            raise ValueError(
                "the uint8 wire never packs on the host: space-to-depth "
                "rides the device-finish prologue (data/device_ingest.py) "
                "— construct with space_to_depth=False")
        super().__init__(lib, batch, image_size, image_dtype)
        self._pack4 = bool(space_to_depth)
        if self._pack4:
            self._out_shape = (image_size // 4, image_size // 4, 48)
        if ranges is None:
            n = len(files)
            if len(labels) != n:
                raise ValueError("labels must match files")
            path_idx, offsets, lengths = _whole_file_ranges(n)
        else:
            path_idx, offsets, lengths = ranges
            if not (len(path_idx) == len(offsets) == len(lengths)
                    == len(labels)):
                raise ValueError("ranges/labels length mismatch")
        self._handle = self._create_ranged(
            files, path_idx, offsets, lengths, labels, seed=seed, mean=mean,
            std=std, num_threads=num_threads, area_range=area_range,
            eval_mode=0, finite=0, pack4=self._pack4)
        #: Flip ownership (ABI v9): False = the fused on-device augmentation
        #: stage owns the horizontal flip and this loader must never flip
        #: (double-flip is structurally impossible because exactly one side
        #: holds the flag). Set immediately after create — the native
        #: workers start lazily on the first next(), so this is race-free,
        #: same contract as restore_state's seek.
        self.hflip = bool(hflip)
        if not self.hflip:
            rc = int(lib.dvgg_jpeg_loader_set_hflip(self._handle, 0))
            if rc != 0:
                raise RuntimeError(
                    f"dvgg_jpeg_loader_set_hflip refused (rc={rc}) — the "
                    "loader already started decoding")
        self._started = False
        register_decode_poller()

    def restore_state(self, step: int) -> bool:
        if self._started:
            return False  # seek is only exact before the first draw
        self._lib.dvgg_jpeg_loader_seek(self._handle, int(step))
        return True

    def __iter__(self):
        return self

    def __next__(self):
        self._started = True
        images, labels, _ = self._next_raw(self._handle)
        return {"image": images, "label": labels}


class NativeJpegEvalIterator(_NativeJpegBase):
    """One finite in-order eval pass: deterministic center crop, no flip.

    Yields {'image', 'label', 'valid'} with `valid` a (B,) bool mask — the
    final partial batch is zero-padded and masked, matching the exact-eval
    protocol (data/eval_pad.py: is_finite + padding_batch, so Trainer.evaluate
    drives it exactly like the tf.data FiniteEvalIterable). Re-iterable: each
    `iter()` restarts the pass with a fresh native handle.
    """

    is_finite = True

    def __init__(self, files: Sequence[str], labels: Sequence[int],
                 batch: int, image_size: int, *,
                 mean: np.ndarray, std: np.ndarray,
                 image_dtype: str = "float32",
                 num_threads: int | None = None,
                 ranges=None):
        lib = load_native_jpeg()
        if lib is None:
            raise RuntimeError("native jpeg loader unavailable")
        if not len(files):
            raise ValueError("empty file list")
        super().__init__(lib, batch, image_size, image_dtype)
        self._files = list(files)
        self._labels = list(labels)
        self._mean = np.ascontiguousarray(mean, np.float32)
        self._std = np.ascontiguousarray(std, np.float32)
        self._num_threads = num_threads
        self._ranges = ranges
        self.num_examples = len(labels)
        self.local_batch = self.batch
        register_decode_poller()

    def __iter__(self):
        # Each pass owns a PRIVATE handle: interleaved iterators read their
        # own streams, and an abandoned generator's cleanup (the finally also
        # runs on GeneratorExit) frees its own C++ workers/buffers without
        # touching any newer pass.
        if self._ranges is None:
            path_idx, offsets, lengths = _whole_file_ranges(len(self._files))
        else:
            path_idx, offsets, lengths = self._ranges
        handle = self._create_ranged(
            self._files, path_idx, offsets, lengths, self._labels, seed=0,
            mean=self._mean, std=self._std, num_threads=self._num_threads,
            area_range=(1.0, 1.0), eval_mode=1, finite=1)
        try:
            while True:
                out = self._next_raw(handle)
                if out is None:
                    break
                images, labels, valid = out
                mask = np.zeros((self.batch,), bool)
                mask[:valid] = True
                yield {"image": images, "label": labels, "valid": mask}
        finally:
            self._destroy(handle)

    def padding_batch(self):
        """All-invalid batch for the uneven-host-shard lockstep protocol
        (data/eval_pad.py)."""
        s = self.image_size
        return {
            "image": np.zeros((self.batch, s, s, 3), self._np_dtype),
            "label": np.zeros((self.batch,), np.int32),
            "valid": np.zeros((self.batch,), np.bool_),
        }
