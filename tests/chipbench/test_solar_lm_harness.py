"""Solar-Open2's cell through the unedited hybrid driver, at a tiny size on
the CPU: it resolves by name and states its cut from the configuration's
own `published`, its timed path agrees with the plain reference, each
planted fault is flagged (the delta rule's own among them), its four
readers read a hand-made table and are restricted to it (None, never 0,
on a trace without the declared names or on another configuration's
facts), and the mechanisms script tells the program from one whose beta
is halved. Nothing here reads a file outside the repository."""

import json
import os

import pytest

from _tiny_solar_lm import CELL, ROOT, context, tiny
from chipbench import run as harness
from chipbench.drivers import hybrid_lm_train as driver
from chipbench.layer_metrics import (gated_gqa_core_roofline_pct,
                                     kda_softplus_core_roofline_pct,
                                     solar_kda_pct, solar_moe_pct)

READERS = {"solar_kda_pct": solar_kda_pct,
           "kda_softplus_core_roofline_pct": kda_softplus_core_roofline_pct,
           "gated_gqa_core_roofline_pct": gated_gqa_core_roofline_pct,
           "solar_moe_pct": solar_moe_pct}
#: the catalog's row, as the configuration file states it
ROW = {"hidden_size": 4096, "num_hidden_layers": 48,
       "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
       "vocab_size": 196608, "intermediate_size": 10240,
       "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
       "rope_theta": 10000, "first_k_dense_replace": 0, "use_rope": False,
       "gqa_interval": 3, "gqa_layers": list(range(0, 48, 4)),
       "use_gqa_gate": True, "kda_use_full_proj": False,
       "kda_allow_neg_eigval": True, "n_routed_experts": 320,
       "n_shared_experts": 1, "norm_topk_prob": True,
       "routed_scaling_factor": 1, "num_experts_per_tok": 8,
       "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                              "num_heads": 64, "num_kv_heads": None}}


def test_the_cell_resolves_and_states_its_cut():
    bench, cell, config = harness.load_cell(ROOT, CELL)
    assert cell["driver"] == "hybrid_lm_train" and cell["chips"] == 1
    assert cell["batch_per_chip"] == 1 and cell["mode"] == "step"
    assert cell["traffic"] == "ep40_step"
    for key, folder, end in (("reference", "reference", ".py"),
                             ("counts", "", ".py"), ("scopes", "", "")):
        assert os.path.isfile(os.path.join(ROOT, "chipbench", folder,
                                           config[key] + end)), key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size", "hybrid_override_pattern"]
    # the cut from the file's own `published`: every key of the row holds
    # its number but the reduced, which `published` gives back
    published = config["published"]
    assert published == {"num_hidden_layers": 48, "n_routed_experts": 320,
                         "vocab_size": 196608,
                         "hybrid_override_pattern": "GKKK" * 12}
    assert {k for k, v in ROW.items() if config[k] != v} \
        == set(config["reduced"]) - {"hybrid_override_pattern"}
    assert all(ROW[k] == published[k] for k in published if k in ROW)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["hybrid_override_pattern"]) \
        == (4, 8, 24576, "GKKK")
    assert config["probe_leaf"] in config["probe_leaves"]
    # the metrics this cell brings are restricted to it, and no other
    # configuration's reader is asked on it
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert {m["name"] for m in mine} == set(READERS)
    assert all(m["moves"] == "train_images_per_s" for m in mine)
    for metric in bench["per_layer"]:
        if "workloads" in metric and metric["name"] not in READERS:
            assert not harness.applies(metric, CELL), metric["name"]
    # the preset states the file's numbers, at every published width, and
    # the file's parameter count is the state's
    import jax
    from distributed_vgg_f_tpu.config import get_config
    from distributed_vgg_f_tpu.models import solar_open2
    from distributed_vgg_f_tpu.models.registry import build_model
    cfg = get_config(config["preset"])
    recipe = driver.recipe_of(cfg, config)
    assert recipe["seq_len"] == 4096 and recipe["global_batch"] == 1
    assert driver.arch_of(config)["n_routed_experts"] == 320
    assert solar_open2.pattern_of(48, config["gqa_layers"]) \
        == published["hybrid_override_pattern"]
    shapes = jax.eval_shape(
        build_model(cfg.model).init, jax.random.key(0),
        jax.ShapeDtypeStruct((1, 128), "int32"))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) \
        == config["parameters"] == 1_295_087_424
    flat = {driver.inputs.leaf_name(path) for path, _ in
            jax.tree_util.tree_leaves_with_path(shapes)}
    assert set(config["probe_leaves"]) <= flat
    for tail in (*config["init"], *config["init_from_uniform"]):
        assert any(name.endswith(tail) for name in flat), tail


def test_the_timed_path_agrees_with_the_reference(tmp_path):
    line = harness.run_cell(context(tmp_path))
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_images_per_s", "peak_hbm_gib",
                                    "setup_s"}
    checks = line["checks"]
    assert checks["dropped_assignments"]["value"] == 0
    assert checks["compiles_in_window"]["value"] == 0
    assert {"loss_gap_step3", "first_grad_gap", "change_gap",
            "first_grad_diff", "probe_grad_diff"} <= set(checks)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "chunk_reset"])
def test_a_broken_timed_path_is_not_correct(tmp_path, fault):
    line = harness.run_cell(context(tmp_path, fault=fault))
    assert line["correct"] is False
    failing = {k for k, c in line["checks"].items()
               if c["value"] > c["limit"]}
    assert failing & {"first_grad_gap", "first_grad_diff", "change_gap",
                      "probe_grad_diff"}, line["checks"]


def _facts(scopes: dict, config=None) -> dict:
    """Facts as the driver hands them, with a hand-made table: 10 traced
    steps, 1 s of device self time."""
    if config is None:
        _, _, config = harness.load_cell(ROOT, CELL)
    lm = {"arch": driver.arch_of(config), "layers": 4, "vocab_rows": 24576,
          "experts_held": 8, "seq_len": 4096, "rows": 1,
          "assignments_held": [102.0] * 4}
    table = {"total_s": 1.0, "phases_found": ["loss"], "scopes": {
        name: {"forward": t / 4, "backward": 3 * t / 4}
        for name, t in scopes.items()}}
    return {"lm": lm, "lm_counts": config["counts"],
            "lm_names": driver.names(config), "scopes": table,
            "traced": {"steps": 10}, "device_kind": "TPU v5 lite",
            "chips": 1}


def test_readers_on_a_hand_made_table():
    facts = _facts({"kda_qkv": 0.1, "kda_conv": 0.05, "kda_gates": 0.05,
                    "kda_core": 0.25, "kda_out": 0.05, "gqa_qkv": 0.02,
                    "gqa_core": 0.2, "gqa_gate": 0.02, "gqa_out": 0.01,
                    "moe_router": 0.02, "moe_dispatch": 0.02,
                    "moe_experts": 0.1, "moe_combine": 0.02,
                    "moe_shared": 0.04, "lm_head": 0.05})
    assert solar_kda_pct.read(facts) == pytest.approx(50.0)
    assert solar_moe_pct.read(facts) == pytest.approx(20.0)
    tokens = 4096
    # the delta rule: bound by its bytes (q, k, v, o one each, g two,
    # beta), 3 layers x 3 passes
    core_bytes = 2 * tokens * 64 * (6 * 128 + 2)
    assert kda_softplus_core_roofline_pct.read(facts) == pytest.approx(
        100 * (3 * 3 * core_bytes / 819e9) * 10 / 0.25, rel=1e-6)
    # the GQA core: 3 passes x 2 S^2 x 64 heads x 128 / 2, twice (q k^T,
    # p v), bound by its operations
    core_s = 3 * 2 * (2 * 4096 ** 2 * 64 * 128 / 2) / 197e12
    assert gated_gqa_core_roofline_pct.read(facts) == pytest.approx(
        100 * core_s * 10 / 0.2, rel=1e-6)
    assert all(0 < reader.read(facts) < 100 for reader in READERS.values())


@pytest.mark.parametrize("name", sorted(READERS))
def test_readers_give_none_without_the_declared_names(name):
    """Another cell's facts (Ling's, whose names file has the same `kda`
    and `moe` groups, and Nemotron's with `gqa_core`); an untraced run; a
    trace with none of the names: None, never 0."""
    reader = READERS[name]
    assert reader.read({"trace_dir": None}) is None
    untraced = {**_facts({"loss": 1.0}), "scopes": None, "traced": None}
    assert reader.read(untraced) is None
    assert reader.read(_facts({"loss": 0.5, "conv1": 0.5})) is None
    for cell in ("ling3_flash_ep64_step", "nemotron3_nano_ep8_step"):
        _, _, other = harness.load_cell(ROOT, cell)
        theirs = {**_facts({"kda_core": 0.3, "moe_experts": 0.3,
                            "gqa_core": 0.4}),
                  "lm_names": driver.names(other),
                  "lm_counts": other["counts"]}
        assert reader.read(theirs) is None, cell


def test_the_mechanisms_script_tells_a_halved_beta():
    """`solar_lm_mechanisms.readings` at a tiny size: the program agrees
    with the reference and sows what the unbounded gate and beta in (0, 2)
    should; the program with beta in (0, 1) is not correct."""
    from chipbench import solar_lm_mechanisms
    _, cell, config = tiny()
    rows = []
    out = solar_lm_mechanisms.readings(
        cell, config, [11], programs=("program", "beta_halved"),
        emit=lambda line, **kw: rows.append(json.loads(line)))
    assert out == {"program": [1, 1], "beta_halved": [0, 1]}
    sown = rows[0]["sown_last_step"]
    assert max(v for k, v in sown.items()
               if k.startswith("kda_beta_max/")) > 1
    assert all(v == 0 for k, v in sown.items()
               if k.startswith("kda_kernel/"))
    assert not any(k.startswith("kda_beta_max/")
                   for k in rows[1]["sown_last_step"])
