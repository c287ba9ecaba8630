"""Each kernel's work function against counts made by hand."""
import tinyroot  # noqa: F401  (puts the repository root on sys.path)

from bench.harness import work_fn


def test_cd_update_work_by_hand():
    # n=1000 rows of width d=54 against a block of B=64:
    # Gram tile 2*1000*64*54 = 6,912,000; contraction 2*1000*64 = 128,000
    # bytes: X 216,000 + s 4,000 + out 4,000 + Xb 13,824 + w 256
    flops, nbytes = work_fn("cd_update")({"n": 1000, "d": 54, "B": 64})
    assert flops == 7_040_000
    assert nbytes == 238_080


def test_cd_update_work_scales_with_width():
    f1, b1 = work_fn("cd_update")({"n": 50_000, "d": 54, "B": 64})
    f2, b2 = work_fn("cd_update")({"n": 50_000, "d": 254, "B": 64})
    assert f2 > f1 and b2 > b1
    # at n=50,000 the call reads ~10.8 MB at d=54 and ~51 MB at d=254
    assert 10.7e6 < b1 < 11.5e6
    assert 50.5e6 < b2 < 51.5e6
