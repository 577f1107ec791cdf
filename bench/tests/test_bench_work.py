"""Operation and byte counts against a brute-force count at small shapes."""

import numpy as np
import pytest

import work


def brute_sgns(lengths, window, negatives, dim):
    flops = 0
    tokens = 0
    live = 0
    for lw in lengths:                        # one lifetime: W walk lengths
        tokens += int(sum(lw))
        for p in range(max(lw)):
            targets = [w for w, length in enumerate(lw) if p < length]
            if not targets:
                continue
            live += 1
            rows = 0
            for w in targets:
                for j in range(p - window, p + window + 1):
                    if j != p and 0 <= j < lw[w]:
                        rows += 1
            flops += 3 * 2 * dim * rows * (len(targets) + negatives)
    bytes_ = (tokens * 4 + live * negatives * 2) * dim * 4
    return flops, bytes_


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sgns_counts_match_brute_force(seed):
    rng = np.random.default_rng(seed)
    t_len, w_cnt = 12, 3
    walks = np.full((5, w_cnt, t_len), -1)
    lengths = rng.integers(0, t_len + 1, size=(5, w_cnt))
    for i in range(5):
        for w in range(w_cnt):
            walks[i, w, :lengths[i, w]] = rng.integers(0, 50, lengths[i, w])
    got = work.sgns_work(work.walk_lengths(walks), 3, 2, 8)
    assert got == pytest.approx(brute_sgns(lengths.tolist(), 3, 2, 8))


def test_padding_is_not_work():
    walks = np.full((1, 2, 100), -1)
    walks[0, :, :20] = 7
    short = work.sgns_work(work.walk_lengths(walks), 10, 5, 128)
    walks[0, :, :100] = 7
    full = work.sgns_work(work.walk_lengths(walks), 10, 5, 128)
    assert full[0] > 4 * short[0] and full[1] == 5 * short[1]


def test_topk_counts_and_roofline():
    flops, bytes_ = work.topk_work(8, 1000, 16)
    assert flops == 2 * 8 * 1000 * 16
    assert bytes_ == 1000 * 16 * 4 + 8 * 1000 * 4
    peaks = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    share, bound = work.roofline_share(flops, bytes_, 1e-3, peaks)
    assert bound == "memory"
    assert share == pytest.approx(100 * bytes_ / 1e9 / 1e-3)
