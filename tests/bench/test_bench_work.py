"""Required operations and bytes against counts made by hand, and the
families' inventories found by name."""
import pytest

from bench import harness, work

DENSE = dict(family="dense", n_layers=2, d_model=8, n_heads=2, n_kv_heads=1,
             d_ff=16, vocab=10, head_dim=4, mlp_act="silu")
HYBRID = dict(family="hybrid", n_layers=2, d_model=8, n_heads=2,
              n_kv_heads=2, d_ff=16, vocab=10, head_dim=4, mlp_act="gelu",
              ssm_state=3, ssm_expand=2, ssm_head_dim=4, attn_every=2)


def test_causal_attention_counts_the_lower_triangle():
    assert work.attention_pairs(1) == 1
    assert work.attention_pairs(4) == 10        # 4 + 3 + 2 + 1
    S, a = 1024, DENSE
    full = 4 * a["n_heads"] * a["head_dim"] * S * S
    assert work.attention(a, 1, S, "bfloat16")["flops"] == pytest.approx(
        full * (S + 1) / (2 * S))


def test_dense_forward_by_hand():
    S = 3
    # per layer: q, o (8x8 each), k, v (8x4 each): 2*S*(64+64+32+32);
    # attention: 2 heads x hd 4 x 4 x 6 pairs; MLP: 3 x 2*S*8*16
    layer = 2 * S * 192 + 4 * 2 * 4 * 6 + 3 * 2 * S * 8 * 16
    unembed = 2 * S * 8 * 10
    ops = work.forward_ops(DENSE, 1, S, S, "bfloat16")
    assert work.flops(ops) == 2 * layer + unembed
    assert work.flops(work.prefill_step_ops(DENSE, 5, S, "bfloat16")) == (
        5 * (2 * layer + 2 * 8 * 10))
    assert work.train_step_flops(DENSE, 5, S, "bfloat16") == (
        3 * 5 * (2 * layer + unembed))


def test_hybrid_forward_by_hand():
    S = 2
    # Mamba2: d_in 16, 4 heads of 4 channels, state 3
    proj = 2 * S * 8 * (2 * 16 + 2 * 3 + 4) + 2 * S * 16 * 8
    conv = 2 * 4 * S * 16
    scan = 5 * S * 4 * 4 * 3
    attn = 2 * S * 8 * (4 + 4) * 4 + 4 * 2 * 4 * 3
    mlp = 2 * 2 * S * 8 * 16
    one_group = 2 * (proj + conv + scan) + attn + mlp
    ops = work.prefill_step_ops(HYBRID, 1, S, "bfloat16")
    assert work.flops(ops) == one_group + 2 * 8 * 10
    assert work.op_work(ops, "ssm_scan")["flops"] == 2 * scan
    assert work.op_work(ops, "conv")["flops"] == 2 * conv


def test_kernel_bytes_read_and_write_once():
    fa = work.attention(DENSE, 2, 16, "bfloat16")
    # q and o with 2 heads, k and v with 1 kv head, hd 4, 2 bytes
    assert fa["bytes"] == 2 * 16 * 4 * (2 * 2 + 2 * 1) * 2
    assert fa["flops"] == 2 * 4 * 2 * 4 * 136
    ss = work.ssm_scan(HYBRID, 1, 8, "bfloat16")
    # X and Y (8 x 4 heads x 4) in bf16; B, C (8 x 3), dt (8 x 4) and
    # the final state (4 x 4 x 3) in f32
    assert ss["bytes"] == 2 * 8 * 16 * 2 + 4 * (2 * 8 * 3 + 8 * 4) + 4 * 48
    rn = work.rmsnorm(10, 8, "bfloat16")
    # elementwise: bytes only
    assert rn == {"flops": 0, "bytes": 2 * 10 * 8 * 2 + 4 * 8}
    mm = work.matmul(2, 3, 5, "float32")
    assert mm == {"flops": 60, "bytes": (6 + 15 + 10) * 4}


def test_prefill_inventory_counts_each_norm_the_model_needs():
    ops = work.prefill_step_ops(DENSE, 2, 16, "bfloat16")
    per = work.rmsnorm(32, 8, "bfloat16")
    last = work.rmsnorm(2, 8, "bfloat16")
    assert work.op_work(ops, "rmsnorm")["bytes"] == (4 * per["bytes"]
                                                     + last["bytes"])
    assert work.op_work(ops, "attention")["flops"] == 2 * work.attention(
        DENSE, 2, 16, "bfloat16")["flops"]
    assert work.op_work(ops, "ssm_scan") is None
    both = work.op_work(ops, "attention", "attention_proj")
    assert both["flops"] == 2 * (work.attention(DENSE, 2, 16, "bfloat16")
                                 ["flops"] + work.attention_proj(
                                     DENSE, 2, 16, "bfloat16")["flops"])
    h = work.prefill_step_ops(HYBRID, 1, 8, "bfloat16")
    # 2 Mamba2 pre-norms + 2 in the shared block at d, 2 gated at d_in
    assert work.op_work(h, "rmsnorm")["bytes"] == (
        4 * work.rmsnorm(8, 8, "bfloat16")["bytes"]
        + 2 * work.rmsnorm(8, 16, "bfloat16")["bytes"]
        + work.rmsnorm(1, 8, "bfloat16")["bytes"])
    assert work.op_work(h, "attention")["flops"] == work.attention(
        HYBRID, 1, 8, "bfloat16")["flops"]


@pytest.mark.parametrize("family", ["dense", "hybrid"])
def test_every_entry_of_a_family_is_an_operation_with_work(family):
    a = DENSE if family == "dense" else HYBRID
    for o in work.forward_ops(a, 2, 8, 8, "bfloat16"):
        assert set(o) == {"op", "flops", "bytes"}
        assert o["flops"] >= 0 and o["bytes"] > 0


def test_a_family_is_found_by_its_file_and_an_unknown_one_is_refused():
    with pytest.raises(harness.BenchError, match="bench/families/moe.py"):
        work.forward_ops(dict(DENSE, family="moe"), 1, 4, 1, "bfloat16")
