"""Operations and bytes the ADC entry scoring needs, whatever implements it.

Each query row scores every entry candidate: per row and candidate M table
lookups and adds.  The candidates' PQ codes (E x M uint8) are read once a
step, each row's ADC table (M x K f32) once, and each row writes one f32
estimate per candidate.  Nothing else is needed: what an implementation
reads beyond this (int32 or lane-padded copies of the codes, the tables
again for every tile of candidates) is what its roofline share leaves out.
"""


def work(rows: int, e: int, m: int, k: int = 256):
    """(operations, bytes) for `rows` query rows against `e` candidates."""
    ops = rows * e * m
    nbytes = e * m + rows * m * k * 4 + rows * e * 4
    return ops, nbytes


if __name__ == "__main__":
    # hand-worked: one full GIST step, 64 rows x 1,024 candidates, M=240:
    # ops 64 * 1,024 * 240 = 15,728,640; bytes: codes 1,024 * 240 =
    # 245,760, tables 64 * 240 * 256 * 4 = 15,728,640, estimates
    # 64 * 1,024 * 4 = 262,144, in all 16,236,544
    assert work(64, 1024, 240) == (15_728_640, 16_236_544)
    print("pq_adc work: ok")
