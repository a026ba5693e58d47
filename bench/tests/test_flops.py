"""The counts of required work, on shapes worked by hand."""
from harness.spec import load_module

ffn = load_module("flops", "ffn_kernel")
kd = load_module("flops", "kd_kernel")
paged = load_module("flops", "paged_kernel")
step = load_module("flops", "moe_lm_train")


def test_ffn_kernel_counts_routed_rows_frozen():
    # 8 tokens x top-2 = 16 routed rows; per row gate, up and down are
    # 2*4*3 = 24 FLOPs each: 72 forward, 72 more for the input gradient
    f, b = ffn.required(tokens=8, top_k=2, d_model=4, d_expert=3,
                        n_experts=5, n_layers=1)
    assert f == 16 * 72 * 2
    # weights 3*5*4*3*2 B read in each of 2 passes; 16 rows of 4 x 2 B
    # in and out, in each of 2 passes
    assert b == 2 * 360 + 2 * 2 * (16 * 4 * 2)
    # the weight gradient is work only for trained experts
    f_trained, _ = ffn.required(tokens=8, top_k=2, d_model=4, d_expert=3,
                                n_experts=5, n_layers=1, frozen=False)
    assert f_trained == 16 * 72 * 3
    f_fwd, _ = ffn.required(tokens=8, top_k=2, d_model=4, d_expert=3,
                            n_experts=5, n_layers=3, train=False)
    assert f_fwd == 3 * 16 * 72


def test_kd_kernel_counts_heads_and_softmaxes():
    f, b = kd.required(tokens=10, d_student=4, vocab=6)
    assert f == 2 * 10 * 4 * 6 + 5 * 10 * 6
    assert b == (4 * 6 + 10 * 4) * 2 + 3 * 10 * 4
    # a frozen teacher adds its head's forward and its softmax only
    ft, _ = kd.required(tokens=10, d_student=4, vocab=6, d_teacher=3)
    assert ft == 2 * 10 * 7 * 6 + 2 * 5 * 10 * 6


def test_paged_kernel_reads_only_the_context():
    f, b = paged.required(context_lens=[5, 3], n_heads=4, n_kv_heads=2,
                          head_dim=8)
    assert f == 4 * 8 * 4 * 8
    assert b == 2 * 8 * 2 * 8 * 2 + 2 * 2 * 4 * 8 * 2


def test_step_counts_frozen_experts_without_weight_gradient():
    a = {"D": 4, "H": 2, "KH": 2, "Dh": 2, "V": 10, "n_dense": 1,
         "n_moe": 1, "F_dense": 6, "E": 3, "k": 2, "F": 5, "F_shared": 5}
    B, S = 1, 4
    T = B * S
    proj = 2 * T * 4 * (2 * 4 + 2 * 4)
    core = 2 * B * S * S * 4            # causal: half of 4 * S^2 * H * Dh
    dense = 3 * (proj + core + 6 * T * 4 * 6)
    moe = 3 * (proj + core + 2 * T * 4 * 3) + 2 * (6 * T * 2 * 4 * 5
                                                   + 6 * T * 4 * 5)
    head = 3 * 2 * T * 4 * 10
    assert step.step_flops(a, B, S) == dense + moe + head
