"""Operations per token against a hand count for cerebras-gpt-1.3b as trained
(4 of 24 blocks, T=2048)."""

from chipbench import flops, harness


def test_gpt_flops_hand_count():
    cfg = harness.load_json(harness.BENCH_DIR, "configs", "cerebras-gpt-1.3b.json")
    d, inner, vocab, layers, T = 2048, 8192, 50257, 4, 2048
    assert (cfg["n_embd"], cfg["n_inner"], cfg["vocab_size"]) == (d, inner, vocab)
    # multiply-adds per token and block: QKV 3d^2, projection d^2, FFN 2*d*inner,
    # attention over (T+1)/2 positions: d for QK^T and d for AV each position
    block = 3 * d * d + d * d + 2 * d * inner + 2 * d * (T + 1) // 2
    assert block == 50_331_648 + 4_196_352
    macs = layers * block + d * vocab
    assert macs == 321_038_336
    assert flops.gpt_forward_flops_per_token(cfg, layers, T) == 2 * macs == 642_076_672
    assert flops.gpt_train_flops_per_token(cfg, layers, T) == 1_926_230_016
    # the ledger's 45.5 k tokens/s (PR 22) would be 44.5% of a v5e's 197 TFLOP/s
    assert abs(1_926_230_016 * 45_503 / 197e12 - 0.445) < 0.001


def test_the_admit_steps_count_is_the_forward_count_at_equal_shapes():
    """One definition of a block's operations under both: a step that carries a
    prompt of T real tokens and no decode row is a forward pass over T tokens
    whose head runs over ONE row, whatever the depth."""
    cfg = harness.load_json(harness.BENCH_DIR, "configs", "cerebras-gpt-1.3b.json")
    d, vocab = cfg["n_embd"], cfg["vocab_size"]
    for layers, T in ((4, 2048), (24, 1984), (24, 1025), (1, 1)):
        forward = T * flops.gpt_forward_flops_per_token(cfg, layers, T)
        assert flops.gpt_admit_step_flops(cfg, layers, T, 0) == forward - 2 * (T - 1) * d * vocab
    # each decode row adds a block's products without attention, and a row of the head
    block = flops.gpt_block_macs_per_row(cfg, 0)
    assert block == 4 * d * d + 2 * d * cfg["n_inner"] == 50_331_648
    one = flops.gpt_admit_step_flops(cfg, 24, 1440, 1) - flops.gpt_admit_step_flops(cfg, 24, 1440, 0)
    assert one == 2 * (24 * block + d * vocab)
    # the cell's mean admission: 1,444 real tokens and 16 rows, 3.8 T operations, 19 ms of a v5e at its peak
    assert 3.7e12 < flops.gpt_admit_step_flops(cfg, 24, 1444, 16) < 3.9e12
