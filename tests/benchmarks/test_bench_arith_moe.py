"""The sparse feed-forward's FLOP and byte counts (``benchmarks/arith_moe.py``)
at the published OLMoE and Mixtral shapes, and the two rules that keep a
roofline share built from them under 100 %."""

import pytest

from benchmarks import arith_moe


def test_one_token_through_one_expert():
    # gate and up 2048 -> 1024, down 1024 -> 2048
    assert arith_moe.expert_pair_flops(2048, 1024) == 2 * 2048 * 1024 * 2 + 2 * 1024 * 2048
    assert arith_moe.expert_pair_flops(2048, 1024, glu=False) == 2 * 2 * 2048 * 1024
    assert arith_moe.expert_weight_bytes(2048, 1024) == 3 * 2048 * 1024 * 2
    # Mixtral: 14336-wide experts of a 4096-wide model, 0.35 GB each in bf16
    assert arith_moe.expert_weight_bytes(4096, 14336) == pytest.approx(0.3523e9, rel=1e-3)


def test_all_experts_executes_every_expert_for_every_token():
    flops = arith_moe.all_experts_flops(512, 2048, 1024, 64, 8)
    mlp = 8 * 512 * 64 * 3 * 2 * 2048 * 1024
    assert flops == mlp + 8 * 2 * 512 * 64 * 2048
    assert flops == pytest.approx(3.30e12, rel=0.01)          # ISSUE 26: 3.3 TFLOP a chunk
    # what the router asked for is k of E of the MLP work
    assert arith_moe.useful_flop_share(8, 64) == 0.125
    assert arith_moe.useful_flop_share(2, 8) == 0.25


@pytest.mark.parametrize("lanes,experts_read", [(1, 8), (4, 32), (8, 64), (16, 64)])
def test_a_decode_step_needs_each_routable_expert_once(lanes, experts_read):
    need = arith_moe.decode_needed_weight_bytes(lanes, 8, 64, 2048, 1024, layers=8)
    assert need == 8 * experts_read * 3 * 2048 * 1024 * 2
    # never more than the bytes the selective gather moves (T*k slices), so a
    # share of the bandwidth peak over the gather's own time cannot pass 100 %
    assert need <= 8 * lanes * 8 * arith_moe.expert_weight_bytes(2048, 1024)
    # ... and never more than every expert once
    assert need <= 8 * 64 * arith_moe.expert_weight_bytes(2048, 1024)
