"""Config registry + analytic parameter counting."""
import jax
import pytest

from repro import configs
from repro.models import registry


def test_registry_lists_all_assigned():
    assert len(configs.ASSIGNED_ARCHS) == 10
    for a in configs.ASSIGNED_ARCHS:
        cfg = configs.get_config(a)
        assert cfg.name.startswith(a.split("-")[0].split(".")[0][:4]) or True
        assert cfg.d_model > 0


def _pad_overhead(cfg) -> int:
    """Implementation padding not in the analytic count: padded vocab rows
    + padded (masked, never-routed) EP experts."""
    from repro.models.transformer import padded_vocab
    pad = padded_vocab(cfg.vocab_size) - cfg.vocab_size
    tied = getattr(cfg, "tie_embeddings", False)
    total = pad * cfg.d_model * (1 if tied else 2)
    if cfg.moe is not None:
        extra = cfg.moe.padded_experts - cfg.moe.num_experts
        total += (extra * (3 * cfg.d_model * cfg.moe.d_ff_expert
                           + cfg.d_model) * cfg.num_layers)
    hp = cfg.padded_heads - cfg.num_heads
    if hp and cfg.family in ("dense", "vlm", "moe"):
        hd = cfg.resolved_head_dim
        per = 2 * hp * hd * cfg.d_model + (hp * hd if cfg.attn_bias else 0)
        total += per * cfg.num_layers
    return total


@pytest.mark.parametrize("arch", configs.ASSIGNED_ARCHS)
def test_analytic_count_matches_init(arch):
    """counting.py formulas == actual initialized leaf sizes (reduced)."""
    cfg = configs.get_reduced(arch)
    model = registry.build(cfg)
    analytic = cfg.param_count()
    actual = sum(x.size for x in jax.tree.leaves(model.init(0)))
    assert actual - _pad_overhead(cfg) == analytic


@pytest.mark.parametrize("arch", configs.ASSIGNED_ARCHS)
def test_table_count_matches_analytic_fullsize(arch):
    """Full-size param tables (no allocation) == analytic formulas."""
    cfg = configs.get_config(arch)
    model = registry.build(cfg)
    assert model.param_count() - _pad_overhead(cfg) == cfg.param_count()


def test_published_sizes():
    """Spot-check against published parameter counts."""
    expect = {
        "qwen2.5-14b": (14.8e9, 0.02),
        "llama3-8b": (8.0e9, 0.01),
        "smollm-135m": (135e6, 0.03),
        "phi3.5-moe-42b-a6.6b": (41.9e9, 0.02),
        "zamba2-7b": (7.0e9, 0.05),
    }
    for arch, (n, tol) in expect.items():
        got = configs.get_config(arch).param_count()
        assert abs(got - n) / n < tol, (arch, got)
    # MoE active params
    assert abs(configs.get_config("phi3.5-moe-42b-a6.6b").active_param_count()
               - 6.6e9) / 6.6e9 < 0.02
    assert abs(configs.get_config("qwen2-moe-a2.7b").active_param_count()
               - 2.7e9) / 2.7e9 < 0.02


def test_rm_generations_hit_paper_curves():
    from repro.configs import rm1, rm2
    assert abs(rm1.size_bytes(0) - 1.4e12) / 1.4e12 < 0.01   # 1.4 TB
    assert abs(rm1.size_bytes(5) - 7.8e12) / 7.8e12 < 0.01   # 7.8 TB


def test_shape_applicability():
    from repro.configs.base import SHAPES, shape_applicable
    long = SHAPES["long_500k"]
    ok, _ = shape_applicable(configs.get_config("llama3-8b"), long)
    assert not ok
    ok, _ = shape_applicable(configs.get_config("rwkv6-3b"), long)
    assert ok
    ok, _ = shape_applicable(configs.get_config("zamba2-7b"), long)
    assert ok


@pytest.mark.parametrize("name, want", [
    # (dense FLOPs, sparse bytes, table bytes, parameters) per sample,
    # as the counts gave them before tables and bags were per table
    ("rm1-chip", (22708992, 32768000, 1677721600, 423745409)),
    ("rm2.v0", (962371840, 8192000, 800000000000, 200477443201)),
    # DLRM-DCNv2 (MLPerf): bottom 340,992 + cross 3 x 4 x 3,456 x 512
    # + top 10,486,272 FLOPs; 214 lookups of 512 B; 4,196,496 rows of
    # 128; 16,044,545 dense parameters
    ("dcnv2-chip", (32060928, 214 * 512, 4196496 * 512,
                    4196496 * 128 + 16044545)),
])
def test_dlrm_counts(name, want):
    from repro.configs import counting, dcnv2, rm1, rm2
    cfg = {"rm1-chip": rm1.CHIP_SHARE, "rm2.v0": rm2.CONFIG,
           "dcnv2-chip": dcnv2.CHIP_SHARE}[name]
    assert (counting.dlrm_dense_flops(cfg), counting.dlrm_sparse_bytes(cfg),
            counting.dlrm_size_bytes(cfg), cfg.param_count()) == want
    assert registry.build(cfg).param_count() == cfg.param_count()


def test_dcnv2_reduced_count_matches_init():
    from repro.configs import dcnv2
    cfg = dcnv2.REDUCED
    params = registry.build(cfg).init(0)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.param_count()
    # each table held at its own rows, no padded (T, R_max, D) stack
    assert [params["embed"][f"t{t}"].shape[0]
            for t in range(cfg.dlrm.num_tables)] == list(cfg.dlrm.rows)


@pytest.mark.parametrize("name, idx_bytes, hash_ops", [
    ("rm1-chip", 800 * 80 * 4, 800 * 80),
    ("dcnv2-chip", 214 * 4, 214),
])
def test_serving_unit_counts_lookups_per_table(name, idx_bytes, hash_ops):
    from repro.configs import counting, dcnv2, rm1
    from repro.core.serving_unit import ServingUnitModel, UnitSpec
    cfg = {"rm1-chip": rm1.CHIP_SHARE, "dcnv2-chip": dcnv2.CHIP_SHARE}[name]
    u = ServingUnitModel(cfg, UnitSpec(2, "cn_1g", 4, "ddr_mn"))
    assert (u.idx_bytes, u.hash_ops) == (idx_bytes, hash_ops)
    assert u.dense_flops == counting.dlrm_dense_flops(cfg)
