"""Fused on-device augmentation (r13): kill-switch structural absence,
eval-never-augments sentinel, mixup restart determinism, flip-ownership
single-sourcing (double-flip impossible across the cache-warm x augment-on
x restart-resume grid), the per-model u8 ≡ host loss-trajectory parity
gates, and the flagship preset pins (augment + ZeRO-1)."""

import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_vgg_f_tpu.config import (
    AugmentConfig,
    DataConfig,
    get_config,
    supports_space_to_depth,
)
from distributed_vgg_f_tpu.data.augment import make_device_augment
from distributed_vgg_f_tpu.data.device_ingest import (
    make_device_finish,
    space_to_depth_batch,
)
from distributed_vgg_f_tpu.parallel.mesh import (
    MeshSpec,
    build_mesh,
    shard_host_batch,
)

MEAN = (123.68, 116.78, 103.94)
STD = (58.393, 57.12, 57.375)

FLAGS_ON = AugmentConfig(enabled=True, hflip=True, mixup_alpha=0.2)


def _mesh8(devices8):
    return build_mesh(MeshSpec(("data",), (8,)), devices=devices8)


class _MiniNet:
    """Tiny flax model standing in for the zoo in step-level gates."""

    def __new__(cls):
        import flax.linen as nn

        class Net(nn.Module):
            @nn.compact
            def __call__(self, x, *, train=False, rngs=None):
                x = nn.Conv(8, (3, 3), strides=(2, 2), dtype=jnp.float32)(x)
                x = nn.relu(x)
                x = x.reshape((x.shape[0], -1))
                return nn.Dense(10, dtype=jnp.float32)(x)

        return Net()


# ------------------------------------------------------------------- config
def test_augment_config_validation():
    with pytest.raises(ValueError, match="crop_jitter"):
        AugmentConfig(crop_jitter=-1)
    with pytest.raises(ValueError, match="mixup_alpha"):
        AugmentConfig(mixup_alpha=-0.1)
    with pytest.raises(ValueError, match="rand_ops"):
        AugmentConfig(rand_ops=-2)
    with pytest.raises(ValueError, match="rand_magnitude"):
        AugmentConfig(rand_magnitude=1.5)
    # ownership predicate: only enabled+hflip moves the flip to the device
    assert not AugmentConfig().owns_hflip
    assert not AugmentConfig(enabled=True, hflip=False).owns_hflip
    assert AugmentConfig(enabled=True).owns_hflip


def test_host_space_to_depth_splits_on_augment():
    """With augmentation enabled the host never packs — the step packs
    AFTER the device-side geometric augments (the ordering contract)."""
    base = DataConfig(name="imagenet", space_to_depth=True)
    assert base.host_space_to_depth is True
    aug = DataConfig(name="imagenet", space_to_depth=True,
                     augment=AugmentConfig(enabled=True))
    assert aug.host_space_to_depth is False
    # augment off is byte-identical to pre-r13: packing decision unchanged
    off = DataConfig(name="imagenet", space_to_depth=True,
                     augment=AugmentConfig(enabled=False, hflip=False))
    assert off.host_space_to_depth is True


def test_flagship_ships_augment_and_zero1():
    """Preset pins: the flagship ships flips+mixup on the u8 wire AND
    ZeRO-1 optimizer-state sharding (ROADMAP item 4 first slice); the zoo
    presets are first-class consumers of the same contract via their
    ingest descriptors — no hand-override back to the raw layout."""
    flag = get_config("vggf_imagenet_dp")
    assert flag.data.augment.enabled and flag.data.augment.hflip
    assert flag.data.augment.mixup_alpha > 0
    assert flag.data.wire == "u8" and flag.data.space_to_depth
    assert flag.mesh.shard_opt_state is True
    for name, model in (("vgg16_imagenet", "vgg16"),
                        ("resnet50_imagenet", "resnet50"),
                        ("vit_s16_imagenet", "vit_s16")):
        cfg = get_config(name)
        assert cfg.data.wire == "u8", f"{name} forfeits the u8 wire"
        assert cfg.data.space_to_depth is False
        assert cfg.data.augment.enabled, f"{name} forfeits device augment"
        assert cfg.mesh.shard_opt_state is True


def test_ingest_descriptors_single_source():
    """The descriptor table is the single source: space-to-depth
    eligibility, the schema validator's zoo list, and the DataConfig
    normalize-constant defaults must all agree with it."""
    from distributed_vgg_f_tpu.models.ingest import (
        IMAGENET_MEAN_RGB,
        IMAGENET_STDDEV_RGB,
        INGEST_DESCRIPTORS,
        ingest_descriptor,
    )
    from distributed_vgg_f_tpu.telemetry.schema import _ZOO_MODELS
    assert set(_ZOO_MODELS) == set(INGEST_DESCRIPTORS)
    assert tuple(DataConfig().mean_rgb) == IMAGENET_MEAN_RGB
    assert tuple(DataConfig().stddev_rgb) == IMAGENET_STDDEV_RGB
    assert ingest_descriptor("vggf").space_to_depth
    for name in ("vgg16", "resnet50", "vit_s16"):
        d = ingest_descriptor(name)
        assert not d.space_to_depth and d.wire == "u8"
        assert not d.accepts_uint8
    # unknown models get the conservative unpacked default
    assert not ingest_descriptor("notamodel").space_to_depth
    # supports_space_to_depth reads the descriptor, not a name literal
    assert supports_space_to_depth("vggf", 224)
    assert not supports_space_to_depth("vgg16", 224)
    assert not supports_space_to_depth("vggf", 225)


def test_zoo_models_refuse_raw_uint8():
    """Every zoo stem refuses raw wire pixels — silent 0..255 training is
    impossible for the whole zoo, not just VGG-F."""
    from distributed_vgg_f_tpu.models.resnet import ResNet50
    from distributed_vgg_f_tpu.models.vgg16 import VGG16
    from distributed_vgg_f_tpu.models.vit import ViT
    for model, size in ((VGG16(num_classes=4, compute_dtype=jnp.float32), 32),
                        (ResNet50(num_classes=4,
                                  compute_dtype=jnp.float32,
                                  bn_axis_name=None), 32),
                        (ViT.s16(num_classes=4,
                                 compute_dtype=jnp.float32), 32)):
        with pytest.raises(TypeError, match="device-finish"):
            jax.eval_shape(
                lambda m=model, s=size: m.init(
                    jax.random.key(0), jnp.zeros((1, s, s, 3), jnp.uint8)))


# ------------------------------------------------------ the stage's algebra
def test_disabled_stage_is_none():
    assert make_device_augment(AugmentConfig(), MEAN, STD) is None
    assert make_device_augment(None, MEAN, STD) is None


def test_augment_stage_shapes_and_guards():
    aug = make_device_augment(
        AugmentConfig(enabled=True, hflip=True, crop_jitter=2,
                      mixup_alpha=0.2, cutmix_alpha=0.2, rand_ops=2),
        MEAN, STD, space_to_depth=True)
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(4, 8, 8, 3)), jnp.float32)
    labels = jnp.arange(4, dtype=jnp.int32)
    out, mix_labels, lam = jax.jit(aug)(jax.random.key(0), x, labels)
    assert out.shape == (4, 2, 2, 48)  # the stage packs
    assert out.dtype == jnp.float32
    assert mix_labels.shape == (4,)
    assert float(lam) == pytest.approx(float(lam))  # finite scalar
    # packed input refused: the stage takes the batch as it arrived
    with pytest.raises(ValueError, match="unpacked"):
        aug(jax.random.key(0), space_to_depth_batch(x), labels)
    # raw wire pixels are the stage's own to finish: same shapes, and the
    # finish's dtype (its `image_dtype`), never uint8
    pixels = jnp.asarray(np.random.default_rng(1).integers(
        0, 256, (4, 8, 8, 3)), jnp.uint8)
    out, _, _ = jax.jit(aug)(jax.random.key(0), pixels, labels)
    assert out.shape == (4, 2, 2, 48) and out.dtype == jnp.float32
    aug16 = make_device_augment(FLAGS_ON, MEAN, STD, image_dtype="bfloat16")
    out, _, _ = jax.jit(aug16)(jax.random.key(0), pixels, labels)
    assert out.shape == (4, 8, 8, 3) and out.dtype == jnp.bfloat16
    # the order a built stage took is its own receipt
    assert aug.permute_on_wire_dtype is False       # rand_ops keeps floats
    assert aug16.permute_on_wire_dtype is True


# ---------------------------------- the order is exact: permute, then finish
#: recipe -> AugmentConfig fields (every one with the flip, as shipped)
RECIPES = {
    "flip": {},
    "flip_mixup": {"mixup_alpha": 0.2},             # the shipped recipe
    "flip_mixup_jitter2": {"mixup_alpha": 0.2, "crop_jitter": 2},
    "cutmix": {"cutmix_alpha": 1.0},
    "mixup_cutmix": {"mixup_alpha": 0.2, "cutmix_alpha": 1.0},
    "rand_ops2": {"mixup_alpha": 0.2, "rand_ops": 2},
}


def _old_order(cfg, rng, images, labels, pack):
    """The prologue as it was until PR 27, written out: normalize -> float32
    flip -> jitter -> rand_ops -> mix -> pack. `_crop_jitter` and
    `_rand_ops` are the module's own, which PR 27 left as they were; what it
    reordered (finish, flip, pairing, mix, pack) is spelled out here."""
    from distributed_vgg_f_tpu.data.augment import _crop_jitter, _rand_ops
    mean = jnp.asarray(MEAN, jnp.float32)
    inv_std = jnp.float32(1.0) / jnp.asarray(STD, jnp.float32)
    x = images
    if x.dtype == jnp.uint8:
        x = (x.astype(jnp.float32) - mean) * inv_std
    x = x.astype(jnp.float32)
    b, h, w, _ = x.shape
    k_flip, k_jit, k_rand, k_mix = jax.random.split(rng, 4)
    if cfg.hflip:
        bits = jax.random.bernoulli(k_flip, 0.5, (b,))
        x = jnp.where(bits[:, None, None, None], x[:, :, ::-1, :], x)
    if cfg.crop_jitter:
        x = _crop_jitter(k_jit, x, cfg.crop_jitter)
    if cfg.rand_ops:
        x = _rand_ops(k_rand, x, mean, inv_std, cfg.rand_ops,
                      cfg.rand_magnitude)
    mix_labels = lam = None
    if cfg.mixup_alpha > 0 or cfg.cutmix_alpha > 0:
        k_perm, k_lam, k_box, k_choice = jax.random.split(k_mix, 4)
        perm = jax.random.permutation(k_perm, b)

        def mixup(x, lam0):
            return lam0, x * lam0 + x[perm] * (1.0 - lam0)

        def cutmix(x, lam0):
            ratio = jnp.sqrt(1.0 - lam0)
            bh = jnp.round(ratio * h).astype(jnp.int32)
            bw = jnp.round(ratio * w).astype(jnp.int32)
            cy = jax.random.randint(k_box, (), 0, h)
            cx = jax.random.randint(jax.random.fold_in(k_box, 1), (), 0, w)
            y0, y1 = (jnp.clip(cy - bh // 2, 0, h),
                      jnp.clip(cy + (bh + 1) // 2, 0, h))
            x0, x1 = (jnp.clip(cx - bw // 2, 0, w),
                      jnp.clip(cx + (bw + 1) // 2, 0, w))
            rows = (jnp.arange(h) >= y0) & (jnp.arange(h) < y1)
            cols = (jnp.arange(w) >= x0) & (jnp.arange(w) < x1)
            mask = (rows[:, None] & cols[None, :])[None, :, :, None]
            lam = 1.0 - ((y1 - y0) * (x1 - x0)).astype(jnp.float32) / (h * w)
            return lam, jnp.where(mask, x[perm], x)

        a, c = cfg.mixup_alpha, cfg.cutmix_alpha
        if a > 0 and c > 0:
            lam_mix = jax.random.beta(k_lam, a, a)
            lam_cut = jax.random.beta(jax.random.fold_in(k_lam, 1), c, c)
            if bool(jax.random.bernoulli(k_choice, 0.5)):
                lam, x = cutmix(x, lam_cut)
            else:
                lam, x = mixup(x, lam_mix)
        elif c > 0:
            lam, x = cutmix(x, jax.random.beta(k_lam, c, c))
        else:
            lam, x = mixup(x, jax.random.beta(k_lam, a, a))
        mix_labels, lam = labels[perm], lam.astype(jnp.float32)
    if pack:
        x = space_to_depth_batch(x)
    return x, mix_labels, lam


@pytest.mark.parametrize("recipe", list(RECIPES))
@pytest.mark.parametrize("pack", [True, False], ids=["pack", "nopack"])
@pytest.mark.parametrize("wire", ["u8", "float32"])
def test_prologue_equals_the_old_order_bit_for_bit(wire, pack, recipe):
    """Permuting 1-byte pixels and normalizing once gives every element the
    float32 operations normalize-first gave it: `==` on every element and
    on the label pairing and lam. Compared operation by operation
    (`jax.disable_jit`): compiled, XLA:CPU contracts `a*b + c` into one FMA
    here and not there as its fusions fall, in the old order as in the new,
    which moves single results by one ulp and says nothing of either."""
    cfg = AugmentConfig(enabled=True, hflip=True, **RECIPES[recipe])
    aug = make_device_augment(cfg, MEAN, STD, space_to_depth=pack)
    assert aug.permute_on_wire_dtype is (cfg.rand_ops == 0)
    pixels = jnp.asarray(np.random.default_rng(5).integers(
        0, 256, (8, 16, 16, 3)), jnp.uint8)
    images = pixels if wire == "u8" else make_device_finish(MEAN, STD)(pixels)
    labels = jnp.arange(8, dtype=jnp.int32)
    with jax.disable_jit():
        for seed in range(4):  # both flips, both arms of mixup_cutmix
            key = jax.random.key(seed)
            want = _old_order(cfg, key, images, labels, pack)
            got = aug(key, images, labels)
            for name, w, g in zip(("images", "mix_labels", "mix_lam"),
                                  want, got):
                if w is None:
                    assert g is None, name
                    continue
                assert g.dtype == w.dtype and g.shape == w.shape, name
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                              err_msg=f"{name}, seed {seed}")


def test_hflip_only_stage_flips_about_half():
    aug = make_device_augment(AugmentConfig(enabled=True, hflip=True),
                              MEAN, STD)
    x = jnp.asarray(np.random.default_rng(1).normal(
        size=(64, 6, 6, 3)), jnp.float32)
    labels = jnp.zeros((64,), jnp.int32)
    out, mix_labels, lam = aug(jax.random.key(3), x, labels)
    assert mix_labels is None and lam is None
    x_np, out_np = np.asarray(x), np.asarray(out)
    same = sum(bool(np.array_equal(out_np[i], x_np[i])) for i in range(64))
    mirrored = sum(bool(np.array_equal(out_np[i], x_np[i, :, ::-1, :]))
                   for i in range(64))
    assert same + mirrored == 64, "flip must be the ONLY transform"
    assert 8 < mirrored < 56, "p=0.5 per-image draw"
    # reproducible from the key: same key, same flips
    out2, _, _ = aug(jax.random.key(3), x, labels)
    np.testing.assert_array_equal(out_np, np.asarray(out2))


def test_rand_ops_stay_in_pixel_range():
    """Photometric ops clip on the 0..255 pixel scale: de-normalizing the
    output must land inside [0, 255] whatever the draw."""
    aug = make_device_augment(
        AugmentConfig(enabled=True, hflip=False, rand_ops=3,
                      rand_magnitude=1.0), MEAN, STD)
    pixels = np.random.default_rng(2).integers(
        0, 256, size=(8, 8, 8, 3)).astype(np.uint8)
    finish = make_device_finish(MEAN, STD)
    x = finish(jnp.asarray(pixels))
    out, _, _ = aug(jax.random.key(9), x, jnp.zeros((8,), jnp.int32))
    p = np.asarray(out) * np.asarray(STD, np.float32) \
        + np.asarray(MEAN, np.float32)
    assert p.min() >= -1e-3 and p.max() <= 255.001


# ------------------------------------------- step integration + kill-switch
def _build_step(mesh, model, device_augment, **kw):
    import optax

    from distributed_vgg_f_tpu.train.step import build_train_step
    tx = optax.sgd(0.05, momentum=0.9)
    step = build_train_step(model, mesh, 1e-4, _dp_plan(mesh, tx),
                            device_finish=make_device_finish(MEAN, STD),
                            device_augment=device_augment, **kw)
    return tx, step


def _dp_plan(mesh, tx=None):
    """Plain data parallelism as an exchange plan (parallel/zero.py)."""
    from distributed_vgg_f_tpu.config import MeshConfig
    from distributed_vgg_f_tpu.parallel.zero import plan_exchange
    return plan_exchange(MeshConfig(), mesh, tx)


def _mini_state(model, tx):
    from distributed_vgg_f_tpu.train.state import TrainState
    return TrainState.create(model, tx, jax.random.key(0),
                             jnp.zeros((1, 16, 16, 3), jnp.float32))


def test_augment_off_step_is_structurally_absent(devices8):
    """data.augment.enabled=false ≡ structurally absent: the lowered train
    step from a disabled config is TEXT-IDENTICAL to one built without the
    stage at all — the kill-switch cannot even change instruction order."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    tx, step_off = _build_step(
        mesh, model, make_device_augment(AugmentConfig(), MEAN, STD))
    _, step_none = _build_step(mesh, model, None)
    state = _mini_state(model, tx)
    batch = shard_host_batch(
        {"image": np.zeros((16, 16, 16, 3), np.uint8),
         "label": np.zeros((16,), np.int32)}, mesh)
    rng = jax.jit(lambda: jax.random.key(1))()
    low_off = step_off.lower(state, batch, rng).as_text()
    low_none = step_none.lower(state, batch, rng).as_text()
    assert low_off == low_none


def test_eval_never_augments(devices8):
    """Sentinel: build_eval_step has no augmentation surface — the lowered
    eval computation is bit-identical between augment-on and augment-off
    trainers, and eval logits/counts are unchanged by the augment config."""
    from distributed_vgg_f_tpu.train.step import build_eval_step
    mesh = _mesh8(devices8)
    model = _MiniNet()
    import optax
    state = _mini_state(model, optax.sgd(0.1))
    finish = make_device_finish(MEAN, STD)
    eval_step = build_eval_step(model, mesh, _dp_plan(mesh),
                                device_finish=finish)
    batch = shard_host_batch(
        {"image": np.random.default_rng(5).integers(
            0, 256, size=(16, 16, 16, 3)).astype(np.uint8),
         "label": np.random.default_rng(6).integers(
             0, 10, size=(16,)).astype(np.int32)}, mesh)
    # the eval builder takes no augment argument at all — the structural
    # half of the sentinel
    import inspect
    assert "augment" not in inspect.signature(build_eval_step).parameters
    counts = {k: int(v) for k, v in
              jax.device_get(eval_step(state, batch)).items()}
    # trainer-level: augment-on and augment-off trainers lower the SAME
    # eval computation (proven on the lowered text, which includes every
    # op), and produce identical counts
    low = eval_step.lower(state, batch).as_text()
    eval_step2 = build_eval_step(model, mesh, _dp_plan(mesh),
                                device_finish=finish)
    assert eval_step2.lower(state, batch).as_text() == low
    counts2 = {k: int(v) for k, v in
               jax.device_get(eval_step2(state, batch)).items()}
    assert counts == counts2


def test_mixup_pairing_deterministic_across_restart(devices8):
    """Same (seed, step) → same permutation/lam: a run rebuilt from
    scratch (fresh step fn + fresh jit — the process-restart equivalent)
    that replays to step k continues with EXACTLY the uninterrupted run's
    losses. The augment key is fold_in(step_rng, AUGMENT_RNG_FOLD), so
    determinism rides the state's step counter, not python state."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    aug = make_device_augment(
        AugmentConfig(enabled=True, hflip=True, mixup_alpha=0.4,
                      cutmix_alpha=0.3, crop_jitter=1), MEAN, STD)
    rng0 = np.random.default_rng(7)
    batches = [
        shard_host_batch(
            {"image": rng0.integers(0, 256, (16, 16, 16, 3)).astype(np.uint8),
             "label": rng0.integers(0, 10, (16,)).astype(np.int32)}, mesh)
        for _ in range(4)]
    base = jax.jit(lambda: jax.random.key(1))()

    def run(n_steps, state=None, step=None, tx=None):
        if step is None:
            tx, step = _build_step(mesh, model, aug)
        if state is None:
            state = _mini_state(model, tx)
        losses = []
        start = int(jax.device_get(state.step))
        for b in batches[start:start + n_steps]:
            state, m = step(state, b, base)
            losses.append(float(jax.device_get(m["loss"])))
        return state, losses, tx, step

    _, cont, _, _ = run(4)  # the uninterrupted run
    # "restart": a brand-new step fn (fresh trace — the process-restart
    # equivalent) replays the first 2 steps...
    tx2, step2 = _build_step(mesh, model, aug)
    state2, first2, _, _ = run(2, tx=tx2, step=step2)
    np.testing.assert_array_equal(cont[:2], first2)
    # ...and yet ANOTHER fresh build continues from the replayed state:
    # the augment draws (mixup pairing included) depend only on
    # (seed, state.step, replica)
    tx3, step3 = _build_step(mesh, model, aug)
    _, tail, _, _ = run(2, state=state2, tx=tx3, step=step3)
    np.testing.assert_array_equal(cont, first2 + tail)


def test_augment_composes_with_zero1_and_accum(devices8):
    """The flagship composition (ZeRO-1 + fused augment) matches plain
    replicated DP step-for-step, and grad accumulation slices the mixup
    label pairing correctly (BN-free model: summed micro-grads equal the
    big-batch gradient exactly)."""
    mesh = _mesh8(devices8)
    model = _MiniNet()
    aug = make_device_augment(FLAGS_ON, MEAN, STD)
    rng0 = np.random.default_rng(11)
    batches = [
        shard_host_batch(
            {"image": rng0.integers(0, 256, (16, 16, 16, 3)).astype(np.uint8),
             "label": rng0.integers(0, 10, (16,)).astype(np.int32)}, mesh)
        for _ in range(3)]
    base = jax.jit(lambda: jax.random.key(1))()

    def run(zero1=False, accum=1):
        import optax

        from distributed_vgg_f_tpu.config import MeshConfig
        from distributed_vgg_f_tpu.parallel.zero import plan_exchange
        from distributed_vgg_f_tpu.train.state import TrainState
        from distributed_vgg_f_tpu.train.step import build_train_step
        tx = optax.sgd(0.05, momentum=0.9)
        sample = jnp.zeros((1, 16, 16, 3), jnp.float32)
        plan = plan_exchange(MeshConfig(shard_opt_state=zero1), mesh, tx,
                             grad_accum_steps=accum)
        if zero1:
            shapes = jax.eval_shape(
                lambda r: TrainState.create(model, tx, r, sample),
                jax.random.key(0))
            plan = plan.bind(shapes.params, shapes.batch_stats)
        step = build_train_step(
            model, mesh, 1e-4, plan,
            device_finish=make_device_finish(MEAN, STD),
            device_augment=aug)
        if zero1:
            shardings = plan.state_shardings(mesh)
            state = jax.jit(
                lambda r: TrainState.create(model, tx, r, sample,
                                            exchange=plan),
                out_shardings=shardings)(jax.random.key(0))
        else:
            state = _mini_state(model, tx)
        losses = []
        for b in batches:
            state, m = step(state, b, base)
            losses.append(float(jax.device_get(m["loss"])))
        return losses

    ref = run()
    z1 = run(zero1=True)
    np.testing.assert_allclose(ref, z1, rtol=2e-6)
    acc = run(accum=2)
    np.testing.assert_allclose(ref, acc, rtol=2e-6)


# -------------------------------------------------- per-model parity gates
@pytest.mark.parametrize("model_name", ["vggf", "vgg16", "resnet50",
                                        "vit_s16"])
def test_zoo_wire_parity_with_augment(model_name, devices8):
    """The acceptance gate, per zoo model: the SAME u8 pixel stream fed
    (a) over the u8 wire + device finish and (b) host-normalized (and
    host-packed where the descriptor says so) produces EQUAL CPU loss
    trajectories — with the fused augmentation ON, since augmentation runs
    post-finish on bit-identical values. Models run at toy size; the wire
    contract is size-independent."""
    import optax

    from distributed_vgg_f_tpu.config import ModelConfig
    from distributed_vgg_f_tpu.models import build_model
    from distributed_vgg_f_tpu.models.ingest import ingest_descriptor
    from distributed_vgg_f_tpu.train.state import TrainState
    from distributed_vgg_f_tpu.train.step import build_train_step
    mesh = _mesh8(devices8)
    size = 32
    desc = ingest_descriptor(model_name)
    model = build_model(ModelConfig(
        name=model_name, num_classes=10, dropout_rate=0.0,
        compute_dtype="float32"))
    s2d = desc.space_to_depth and size % 4 == 0
    aug = make_device_augment(FLAGS_ON, MEAN, STD, space_to_depth=s2d)
    rng0 = np.random.default_rng(13)
    pixels = [rng0.integers(0, 256, (8, size, size, 3)).astype(np.uint8)
              for _ in range(2)]
    labels = [rng0.integers(0, 10, (8,)).astype(np.int32) for _ in range(2)]
    mean = np.asarray(MEAN, np.float32)
    inv = np.float32(1.0) / np.asarray(STD, np.float32)

    def run(as_u8):
        tx = optax.sgd(0.05, momentum=0.9)
        state = TrainState.create(
            model, tx, jax.random.key(0),
            jnp.zeros((1, size, size, 3), jnp.float32))
        step = build_train_step(
            model, mesh, 1e-4, _dp_plan(mesh, tx),
            device_finish=make_device_finish(MEAN, STD),
            device_augment=aug)
        base = jax.jit(lambda: jax.random.key(1))()
        losses = []
        for px, lb in zip(pixels, labels):
            # host wire ships the normalized floats; with augmentation on
            # the host never packs (host_space_to_depth) — both wires
            # arrive unpacked and the stage packs post-augment
            images = px if as_u8 else (px.astype(np.float32) - mean) * inv
            b = shard_host_batch({"image": images, "label": lb}, mesh)
            state, m = step(state, b, base)
            losses.append(float(jax.device_get(m["loss"])))
        return losses

    np.testing.assert_array_equal(run(True), run(False))


# ------------------------------------------ the lowered step moves bytes
_MOVERS = ("stablehlo.reverse", "stablehlo.gather", "stablehlo.transpose",
           "stablehlo.reshape", "stablehlo.select",
           "stablehlo.dynamic_slice", "stablehlo.concatenate")


def _image_movers(text, numel):
    """(op, element type) of every operation of a lowered module that only
    moves elements and touches a tensor as large as the image batch."""
    import re
    found = []
    for line in text.splitlines():
        op = next((m for m in _MOVERS if m in line), None)
        if op is None:
            continue
        for dims, dtype in re.findall(r"tensor<((?:\d+x)+)(\w+)>", line):
            if dtype != "i1" and np.prod(
                    [int(d) for d in dims.split("x") if d]) == numel:
                found.append((op.split(".")[1], dtype))
    return found


@pytest.mark.parametrize("preset,packs", [("vggf_imagenet_dp", True),
                                          ("resnet50_imagenet", False)])
def test_lowered_step_permutes_the_u8_batch(preset, packs):
    """The shipped recipe (flip + mixup) on the u8 wire, lowered at a tiny
    size: between the step's image argument and the stem every operation
    that only moves the batch's pixels (the flip's reverse and select, the
    partner's gather with its views, the pack) takes uint8, and a packing
    model never sees a float32 tensor of the unpacked shape at all. The
    stage's receipt says the same."""
    from distributed_vgg_f_tpu.config import apply_overrides
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    rows, size = 8, 32
    extra = {} if packs else {"model.extra": {"stage_sizes": (1, 1, 1, 1)}}
    cfg = apply_overrides(get_config(preset), {
        "data.image_size": size, "model.num_classes": 10,
        "data.global_batch_size": rows, "mesh.num_data": 1, **extra})
    aug = cfg.data.augment
    assert aug.enabled and aug.hflip and aug.mixup_alpha > 0
    assert not (aug.crop_jitter or aug.cutmix_alpha or aug.rand_ops)
    assert cfg.data.space_to_depth is packs
    mesh = build_mesh(MeshSpec((cfg.mesh.data_axis,), (1,)),
                      jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh,
                      logger=MetricLogger(stream=io.StringIO()))
    assert trainer.device_augment.permute_on_wire_dtype is True
    state = jax.eval_shape(trainer.init_state)
    batch = {"image": jax.ShapeDtypeStruct((rows, size, size, 3), jnp.uint8),
             "label": jax.ShapeDtypeStruct((rows,), jnp.int32)}
    text = trainer.train_step.lower(state, batch,
                                    trainer.base_rng()).as_text()
    movers = _image_movers(text, rows * size * size * 3)
    moved = {op for op, _ in movers}
    assert {"reverse", "gather", "select"} <= moved, moved
    assert ("reshape" in moved) or not packs
    assert {dtype for _, dtype in movers} == {"ui8"}, movers
    unpacked_f32 = f"tensor<{rows}x{size}x{size}x3xf32>"
    if packs:
        assert unpacked_f32 not in text
        assert f"tensor<{rows}x{size // 4}x{size // 4}x48xf32>" in text
    else:
        assert unpacked_f32 in text  # the finish's output, then arithmetic


# ------------------------------------------------------- trainer + JSONL
def test_trainer_fit_emits_augment_receipts(tmp_path):
    """A tiny augmented fit: the per-window JSONL carries the
    schema-validated `augment` block, the start record the augment flag,
    and the registry the augment/steps counter + enabled gauge."""
    import json

    from distributed_vgg_f_tpu import telemetry
    from distributed_vgg_f_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, ModelConfig, OptimConfig,
        TrainConfig)
    from distributed_vgg_f_tpu.telemetry.schema import (
        validate_metrics_record)
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger
    telemetry.reset()
    cfg = ExperimentConfig(
        name="augment_fit_smoke",
        model=ModelConfig(name="vggf", num_classes=10,
                          compute_dtype="float32", dropout_rate=0.0),
        optim=OptimConfig(base_lr=0.01, reference_batch_size=16),
        data=DataConfig(name="synthetic", image_size=32,
                        global_batch_size=16, num_train_examples=64,
                        space_to_depth=True,
                        augment=AugmentConfig(enabled=True, hflip=True,
                                              mixup_alpha=0.2)),
        mesh=MeshConfig(num_data=8),
        train=TrainConfig(steps=4, log_every=2, seed=0),
    )
    jsonl = str(tmp_path / "metrics.jsonl")
    trainer = Trainer(cfg, logger=MetricLogger(jsonl_path=jsonl,
                                               stream=io.StringIO()))
    assert trainer.device_augment is not None
    trainer.fit(trainer.init_state())
    records = [json.loads(ln) for ln in open(jsonl)
               if ln.strip()]
    for r in records:
        assert validate_metrics_record(r) == [], r
    start = next(r for r in records if r["event"] == "start")
    assert start["augment"] is True
    trains = [r for r in records if r["event"] == "train"]
    assert trains and all("augment" in r for r in trains)
    assert trains[0]["augment"]["host_flips_disabled"] is True
    snap = telemetry.get_registry().snapshot_split()
    assert snap["counters"].get("augment/steps") == 4
    assert snap["gauges"].get("augment/enabled") == 1
    # the order the built stage took, not the config restated
    assert trainer.device_augment.permute_on_wire_dtype is True
    assert snap["gauges"].get("augment/permute_on_wire_dtype") == 1
    telemetry.reset()
    telemetry.configure(enabled=True)


def test_trainer_augment_off_is_byte_identical_trajectory():
    """Kill-switch trajectory pin: enabled=false trains the EXACT pre-r13
    stream — losses byte-identical to a config that never mentions
    augmentation."""
    from distributed_vgg_f_tpu.config import (
        DataConfig, ExperimentConfig, MeshConfig, ModelConfig, OptimConfig,
        TrainConfig)
    from distributed_vgg_f_tpu.train.trainer import Trainer
    from distributed_vgg_f_tpu.utils.logging import MetricLogger

    def losses(augment_cfg):
        cfg = ExperimentConfig(
            name="augment_off_pin",
            model=ModelConfig(name="vggf", num_classes=10,
                              compute_dtype="float32", dropout_rate=0.0),
            optim=OptimConfig(base_lr=0.01, reference_batch_size=16),
            data=DataConfig(name="synthetic", image_size=32,
                            global_batch_size=16, num_train_examples=64,
                            augment=augment_cfg),
            mesh=MeshConfig(num_data=8),
            train=TrainConfig(steps=3, seed=0),
        )
        trainer = Trainer(cfg, logger=MetricLogger(stream=io.StringIO()))
        assert trainer.device_augment is None
        state = trainer.init_state()
        ds = trainer.make_dataset("train")
        out = []
        rng = trainer.base_rng()
        for _ in range(3):
            state, m = trainer.train_step(state, trainer.shard(next(ds)),
                                          rng)
            out.append(float(jax.device_get(m["loss"])))
        return out

    np.testing.assert_array_equal(
        losses(AugmentConfig()),
        losses(AugmentConfig(enabled=False, hflip=False, mixup_alpha=0.9)))


# ---------------------------------------------- flip ownership (native grid)
_native = None


def _native_available():
    global _native
    if _native is None:
        from distributed_vgg_f_tpu.data.native_jpeg import load_native_jpeg
        _native = load_native_jpeg() is not None
    return _native


requires_native = pytest.mark.skipif(
    not _native_available() if True else False,
    reason="native jpeg loader unavailable")


def _imagefolder(tmp_path, n_classes=2, per_class=6, hw=(40, 44)):
    from PIL import Image
    rng = np.random.default_rng(0)
    files, labels = [], []
    for c in range(n_classes):
        d = tmp_path / f"train/class_{c}"
        d.mkdir(parents=True)
        for i in range(per_class):
            p = d / f"img_{i}.jpg"
            Image.fromarray(rng.integers(0, 256, size=(*hw, 3))
                            .astype(np.uint8)).save(p, "JPEG", quality=90)
            files.append(str(p))
            labels.append(c)
    return files, labels


@requires_native
def test_double_flip_structurally_impossible(tmp_path):
    """The satellite grid: cache-warm x augment-on x restart-resume. With
    device-side augmentation owning flips, every host surface — the native
    decoder, the snapshot cache's warm redraw, and resumed streams — must
    serve the IDENTICAL unflipped pixels: byte-equality against the
    hflip=False reference stream in every cell, so no cell exists where a
    host flip could compose with the device flip."""
    from distributed_vgg_f_tpu.config import (
        DataConfig, SnapshotCacheConfig)
    from distributed_vgg_f_tpu.data import build_dataset
    from distributed_vgg_f_tpu.data.native_jpeg import (
        NativeJpegTrainIterator)
    from distributed_vgg_f_tpu.data.snapshot_cache import (
        SnapshotCachingTrainIterator, shuffle_indices)
    files, labels = _imagefolder(tmp_path)
    n = len(files)
    batch, size, seed = 4, 32, 5
    mean = np.asarray(MEAN, np.float32)
    std = np.asarray(STD, np.float32)
    # the imagefolder builder deterministically permutes the file list
    # with the seed before creating the loader — the reference stream must
    # see the same item order
    order = np.random.default_rng(seed).permutation(n)
    files = [files[i] for i in order]
    labels = [labels[i] for i in order]

    def reference(num_batches, start=0):
        it = NativeJpegTrainIterator(files, labels, batch=batch,
                                     image_size=size, seed=seed, mean=mean,
                                     std=std, num_threads=1, hflip=False)
        try:
            if start:
                assert it.restore_state(start)
            return [next(it) for _ in range(num_batches)]
        finally:
            it.close()

    ref = reference(6)  # two epochs, flips-off ground truth

    def data_cfg(cache_dir=None):
        return DataConfig(
            name="imagenet", data_dir=str(tmp_path), image_size=size,
            global_batch_size=batch, native_threads=1, backend="native",
            augment=AugmentConfig(enabled=True, hflip=True,
                                  mixup_alpha=0.2),
            snapshot_cache=(SnapshotCacheConfig(enabled=True,
                                                dir=str(cache_dir))
                            if cache_dir else SnapshotCacheConfig()))

    # cell 1: augment-on loader — host flips disabled at the source
    ds = build_dataset(data_cfg(), "train", seed=seed)
    assert isinstance(ds, NativeJpegTrainIterator)
    assert ds.hflip is False
    try:
        for b, r in zip([next(ds) for _ in range(6)], ref):
            np.testing.assert_array_equal(b["image"], r["image"])
    finally:
        ds.close()
    # ...while a host-owned-flips loader (augment off) DOES flip: every
    # item is the reference crop or its mirror, and some are mirrored
    ds_flip = NativeJpegTrainIterator(files, labels, batch=batch,
                                      image_size=size, seed=seed, mean=mean,
                                      std=std, num_threads=1)
    try:
        mirrored = 0
        for b, r in zip([next(ds_flip) for _ in range(3)], ref[:3]):
            for i in range(batch):
                got, want = b["image"][i], r["image"][i]
                if np.array_equal(got, want):
                    continue
                np.testing.assert_array_equal(got, want[:, ::-1, :])
                mirrored += 1
        assert mirrored > 0
    finally:
        ds_flip.close()

    # cell 2: restart-resume (no cache) — resumed stream stays unflipped
    resumed = build_dataset(data_cfg(), "train", seed=seed)
    try:
        assert resumed.restore_state(3)
        for b, r in zip([next(resumed) for _ in range(3)], ref[3:6]):
            np.testing.assert_array_equal(b["image"], r["image"])
    finally:
        resumed.close()

    # cell 3: cache cold pass + warm epochs — warm serving never redraws
    # the flip (epoch-0 crops re-served bit-identically, reordered)
    cache_dir = tmp_path / "snap"
    ds = build_dataset(data_cfg(cache_dir), "train", seed=seed)
    assert isinstance(ds, SnapshotCachingTrainIterator)
    assert ds._hflip is False
    try:
        cold = [next(ds) for _ in range(3)]  # epoch 0: cold capture
        for b, r in zip(cold, ref[:3]):
            np.testing.assert_array_equal(b["image"], r["image"])
        by_idx = {}
        order0 = shuffle_indices(n, seed, 0)
        for bi, b in enumerate(cold):
            for j in range(batch):
                by_idx[int(order0[(bi * batch + j) % n])] = b["image"][j]
        warm = [next(ds) for _ in range(6)]  # epochs 1-2: warm serving
        for e in (1, 2):
            order = shuffle_indices(n, seed, e)
            for bi in range(3):
                b = warm[(e - 1) * 3 + bi]
                for j in range(batch):
                    idx = int(order[bi * batch + j])
                    np.testing.assert_array_equal(
                        b["image"][j], by_idx[idx],
                        err_msg=f"warm epoch {e} redrew a flip (item "
                                f"{idx}) despite device-owned flips")
    finally:
        ds.close()

    # cell 4: cache-warm x restart-resume — a NEW wrapped iterator over
    # the same (complete) store resumes mid-warm-stream, still unflipped
    ds2 = build_dataset(data_cfg(cache_dir), "train", seed=seed)
    try:
        assert ds2.restore_state(4)
        got = [next(ds2) for _ in range(2)]
        np.testing.assert_array_equal(got[0]["image"], warm[1]["image"])
        np.testing.assert_array_equal(got[1]["image"], warm[2]["image"])
    finally:
        ds2.close()


@requires_native
def test_native_hflip_switch_contracts(tmp_path):
    """ABI v9 surface: the per-loader switch refuses after the stream
    started; decode_single reproduces the flips-disabled crop; the crop
    geometry is identical at both settings (drawn-but-ignored RNG)."""
    import io as _io

    from PIL import Image

    from distributed_vgg_f_tpu.data.native_jpeg import (
        NativeJpegTrainIterator, decode_single_image, load_native_jpeg)
    rng = np.random.default_rng(3)
    buf = _io.BytesIO()
    Image.fromarray(rng.integers(0, 256, size=(48, 52, 3))
                    .astype(np.uint8)).save(buf, "JPEG", quality=90)
    data = buf.getvalue()
    zero, one = np.zeros(3, np.float32), np.ones(3, np.float32)
    flipped_seeds = 0
    for s in range(8):
        on = decode_single_image(data, 16, zero, one, rng_seed=s)
        off = decode_single_image(data, 16, zero, one, rng_seed=s,
                                  hflip=False)
        if np.array_equal(on, off):
            continue
        np.testing.assert_array_equal(on, off[:, ::-1, :])
        flipped_seeds += 1
    assert 0 < flipped_seeds < 8
    # set_hflip after the first draw is too late — refused, not raced
    files, labels = _imagefolder(tmp_path, n_classes=1, per_class=4)
    it = NativeJpegTrainIterator(files, labels, batch=2, image_size=16,
                                 seed=0, mean=zero, std=one, num_threads=1)
    try:
        next(it)
        lib = load_native_jpeg()
        assert int(lib.dvgg_jpeg_loader_set_hflip(it._handle, 0)) == -1
        assert int(lib.dvgg_jpeg_loader_hflip(it._handle)) == 1
    finally:
        it.close()
