"""``bench/counts/hybrid_lm.py`` by hand for the granite cell: the counted
FLOPs and samples of one round of ``granite4h_micro.s2.recompute.seq2048.k2``
at the published widths."""
import json

import pytest

from conftest import ROOT
from bench import families, flops

counts = families.module("hybrid_lm", "counts")
CELL = "granite4h_micro.s2.recompute.seq2048.k2"
CFG = json.loads((ROOT / "bench/configs/granite4h_micro.json").read_text())
TRAFFIC = json.loads((ROOT / "bench/traffic/s2.recompute.seq2048.k2.json")
                     .read_text())

# Multiply-adds over one row of S = 2048 tokens. d = 2048, d_inner = 4096,
# N = 128, 64 SSD heads of 64, chunk 256, 32 query and 8 KV heads of 64,
# MLP 8192 (proxy layers: 2048), vocabulary slice 12544.
#
# Mamba-2 layer: per token in_proj 2048*8512 = 17,432,576, out_proj
# 4096*2048 = 8,388,608, conv 4352*4 = 17,408, MLP 3*2048*8192 =
# 50,331,648: 76,170,240 x 2048 = 155,996,651,520; SSD: 8 chunks of
# 256*257/2 = 32,896 pairs, each C.B (128) and 64 heads x 64 (4096):
# 263,168 * 4224 = 1,111,621,632; chunk states and C h, 2048 * 64*64*128
# * 2 = 2,147,483,648.
MAMBA = 155_996_651_520 + 1_111_621_632 + 2_147_483_648
# attention layer: per token q, k, v, o 2048*3072 + 2048*2048 =
# 10,485,760 and the MLP 50,331,648: 60,817,408 x 2048 =
# 124,554,051,584; q k^T and p v over 2048*2049/2 = 2,098,176 pairs, 32
# heads x 128: 8,594,128,896
ATTENTION = 124_554_051_584 + 8_594_128_896
# proxy layer: the same attention, MLP 3*2048*2048 = 12,582,912 a token:
# 23,068,672 x 2048 = 47,244,640,256, plus the 8,594,128,896 of the pairs
PROXY = 47_244_640_256 + 8_594_128_896
HEAD = 2047 * 2048 * 12544          # the 2047 positions that predict
# stage 2 of 5 blocks of 2: layers 0-3 frozen (Mamba-2), 4 (Mamba-2) and 5
# (attention) trained, two proxy layers for blocks 3 and 4
PREFIX = 4 * MAMBA
TRAINED = MAMBA + ATTENTION + 2 * PROXY + HEAD
PER_ROW = 2 * PREFIX + 3 * 2 * TRAINED


def test_parts_by_hand():
    assert MAMBA == 159_255_756_800
    assert ATTENTION == 133_148_180_480
    parts = counts.part_macs(CFG, 2, 2048)
    assert parts == {"prefix": PREFIX, "active": MAMBA + ATTENTION,
                     "head": 2 * PROXY + HEAD}


def test_round_by_hand():
    """2 clients x 8 steps x 2 rows; the prefix recomputed in the round
    (no cache tier): 4,014,060,863,488 FLOPs a row, 128.45 TFLOP a round."""
    assert flops.samples_per_round(TRAFFIC) == 32
    assert PER_ROW == 4_014_060_863_488
    assert flops.flops_per_round(CFG, TRAFFIC) == pytest.approx(
        32 * PER_ROW, rel=1e-12)
    # a cached prefix would drop the prefix's forward
    cached = dict(TRAFFIC, cache_tier="f32")
    assert flops.flops_per_round(CFG, cached) == pytest.approx(
        32 * (PER_ROW - 2 * PREFIX), rel=1e-12)


def test_cell_is_listed():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite4h_micro", "s2.recompute.seq2048.k2", 1)
