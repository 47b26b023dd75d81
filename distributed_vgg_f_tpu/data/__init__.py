"""Input pipelines (SURVEY.md §2.1 #5): host-side data feeding the device mesh.

`build_dataset(cfg.data, ...)` returns an iterator of process-local numpy batches
{'image': (B_local, H, W, 3) float32, 'label': (B_local,) int32} (or, for a
language model, {'tokens': (B_local, S + 1) int32}); the trainer
shards them over the mesh with `parallel.mesh.shard_host_batch`.
"""

from distributed_vgg_f_tpu.data.synthetic import SyntheticDataset  # noqa: F401


def build_dataset(data_cfg, split: str = "train", *, seed: int = 0,
                  num_shards: int = 1, shard_index: int = 0,
                  state_dir: str = "", snapshot_every: int = 0,
                  num_classes: int | None = None, seq_len: int = 0):
    """Dataset factory. Per-host sharding: each process gets 1/num_shards of the
    global batch (the reference's per-worker shard, SURVEY.md §1).

    `state_dir`/`snapshot_every` enable deterministic-resume iterator
    snapshots for pipelines that support them (imagenet tf.data train).

    `num_classes` is the MODEL's head width; real datasets have intrinsic
    label spaces, but synthetic labels must stay inside the head — a
    1000-class synthetic label against a 10-class head is an out-of-range
    CE gather (r3: surfaced as loss=nan with finite grads when overriding
    model.num_classes under the synthetic pipeline).

    `data.name == "synthetic_tokens"` is the language model's source
    (data/synthetic_tokens.py): `seq_len` is the model's (its preset's
    `model.extra`), `num_classes` the vocabulary rows it holds. It has a
    train split only."""
    if data_cfg.global_batch_size % num_shards != 0:
        raise ValueError(
            f"global batch {data_cfg.global_batch_size} not divisible by "
            f"{num_shards} host shards")
    local_batch = data_cfg.global_batch_size // num_shards
    # Disaggregated ingest (r16, data/service_client.py): the TRAIN stream
    # comes from the decode-worker fleet instead of local decode. The
    # kill-switch contract mirrors r6-r14: enabled=false (the default)
    # takes none of this branch — local ingest byte-identical, pinned in
    # tests/test_ingest_service.py. Eval always decodes locally (the
    # exact finite pass has no service protocol and no throughput problem).
    svc = getattr(data_cfg, "service", None)
    if svc is not None and svc.enabled and split == "train":
        from distributed_vgg_f_tpu.data.service_client import (
            build_service_client)
        return build_service_client(
            data_cfg, local_batch, seed=seed, num_shards=num_shards,
            shard_index=shard_index, num_classes=num_classes,
            state_dir=state_dir, snapshot_every=snapshot_every)
    if data_cfg.name == "synthetic_tokens":
        if split != "train":
            raise ValueError("synthetic_tokens has a train split only")
        from distributed_vgg_f_tpu.data.synthetic_tokens import (
            SyntheticTokens)
        return SyntheticTokens(local_batch, seq_len, num_classes,
                               seed=seed + shard_index)
    if data_cfg.name == "synthetic":
        return SyntheticDataset(
            batch_size=local_batch, image_size=data_cfg.image_size,
            num_classes=num_classes or _num_classes(data_cfg),
            seed=seed + shard_index,
            num_examples=data_cfg.num_train_examples,
            image_dtype=data_cfg.image_dtype,
            # host_space_to_depth: with device-side augmentation enabled
            # the host ships unpacked and the train step packs post-augment
            space_to_depth=data_cfg.host_space_to_depth
            and split == "train")
    if data_cfg.name == "teacher":
        from distributed_vgg_f_tpu.data.teacher import build_teacher
        return build_teacher(data_cfg, split, local_batch, seed=seed,
                             num_shards=num_shards, shard_index=shard_index)
    if data_cfg.name == "cifar10":
        from distributed_vgg_f_tpu.data.cifar10 import build_cifar10
        return build_cifar10(data_cfg, split, local_batch, seed=seed,
                             num_shards=num_shards, shard_index=shard_index)
    if data_cfg.name == "imagenet":
        from distributed_vgg_f_tpu.data.imagenet import build_imagenet
        return build_imagenet(data_cfg, split, local_batch, seed=seed,
                              num_shards=num_shards, shard_index=shard_index,
                              state_dir=state_dir,
                              snapshot_every=snapshot_every)
    raise KeyError(f"unknown dataset {data_cfg.name!r}")


def _num_classes(data_cfg) -> int:
    return {"cifar10": 10, "teacher": 10}.get(data_cfg.name, 1000)
