"""Operations and bytes the beam hop loop needs, whatever implements it.

Per query and hop the loop reads one adjacency row (R int32 ids) and the R
PQ code rows of those neighbours (R x M uint8), and makes R x M table
lookups and adds.  Per query it reads its ADC table once (M x K f32).
Nothing else is needed: what an implementation reads beyond this (padding,
whole-corpus slabs, f32 copies of the codes) is what its roofline share
leaves out.
"""


def work(queries: int, hops: int, r: int, m: int, k: int = 256):
    """(operations, bytes) for `queries` queries of `hops` hops each."""
    expansions = queries * hops
    ops = expansions * r * m
    nbytes = expansions * (r * 4 + r * m) + queries * m * k * 4
    return ops, nbytes


if __name__ == "__main__":
    # hand-worked: one full SIFT step, 64 queries x 256 hops, R=32, M=64:
    # 64 * 256 = 16,384 expansions; ops 16,384 * 32 * 64 = 33,554,432;
    # bytes 16,384 * (128 + 2,048) = 35,651,584 plus tables
    # 64 * 64 * 256 * 4 = 4,194,304, in all 39,845,888
    assert work(64, 256, 32, 64) == (33_554_432, 39_845_888)
    print("beam_hops work: ok")
