"""bench/work.py against counts made by hand at a toy size."""
from bench import work


def test_one_launch_by_hand():
    # N = 16 (log2 N = 4, an NTT over r limbs = r * 8 * 4 = 32 r), L = 2,
    # k = 1, beta = 3 -> alpha = 1. At level 2: nb = 3 digits of 1 limb,
    # E = 4 extended limbs. One set with zs (0, 1, -1), one input.
    got = work.hlt_launch(N=16, L=2, k=1, beta=3, level=2,
                          diag_sets=[(0, 1, -1)], n_inputs=1)
    ntt = lambda r: 32 * r                                    # noqa: E731
    hoist = ntt(3) + 3 * (16 + 3 * 16 + ntt(3)) + 2 * 3 * 16  # 96+480+96
    keyip = 2 * (2 * 3 * 4 * 16)                              # 2 rotations
    diagip = 3 * (2 * 4 * 16)
    moddown = 2 * (ntt(2) + 2 * 16 + 2 * 2 * 16 + ntt(2) + 2 * 16)
    assert hoist == 672 and keyip == 768 and diagip == 384 and moddown == 512
    assert got["modmults"] == hoist + keyip + diagip + moddown == 2336
    # bytes: input 2*3*16, keys z=1 and z=-1 (slot 7): 2 * 2*3*4*16,
    # diagonals 3*4*16, output 2*2*16 words
    words = 96 + 2 * 384 + 192 + 64
    assert got["bytes"] == 4 * words == 4480


def test_keys_are_counted_once_per_launch_and_identity_is_free():
    one = work.hlt_launch(16, 2, 1, 3, 2, [(0, 1), (1, 2)], 2)
    two = work.hlt_launch(16, 2, 1, 3, 2, [(0, 1)], 2)
    # the second set adds one diagonal row, one output and one new key (z=2)
    extra_words = 4 * 16 + 2 * 2 * 16 + 2 * 3 * 4 * 16
    assert one["bytes"] - two["bytes"] == 4 * (4 * 16 + extra_words)
    # a rotation by slots (N/2 = 8) is the identity: no key
    same = work.hlt_launch(16, 2, 1, 3, 2, [(0, 8)], 1)
    base = work.hlt_launch(16, 2, 1, 3, 2, [(0, 1)], 1)
    assert base["bytes"] - same["bytes"] == 4 * 2 * 3 * 4 * 16


def test_digits_follow_alpha():
    # beta = 1 at L = 2: one digit of 3 limbs at level 2, of 2 at level 1
    a = work.hlt_launch(16, 2, 1, 1, 2, [(0,)], 1)
    ntt = lambda r: 32 * r                                    # noqa: E731
    hoist = ntt(3) + (3 * 16 + 3 * 1 * 16 + ntt(1)) + 2 * 3 * 16
    diag = 2 * 4 * 16
    moddown = 2 * (ntt(2) + 2 * 16 + 2 * 2 * 16 + ntt(2) + 2 * 16)
    assert a["modmults"] == hoist + diag + moddown


def test_hemm_sums_both_steps_and_least_time_names_its_bound():
    params = {"logN": 4, "L": 3, "k": 1, "beta": 4}
    s1, s2 = [(0, 1), (0, -1)], [(0,), (1,), (0, 2), (3,)]
    total = work.hemm(params, s1, s2)
    one = work.hlt_launch(16, 3, 1, 4, 3, s1, 2)
    two = work.hlt_launch(16, 3, 1, 4, 2, s2, 2)
    assert total == {"modmults": one["modmults"] + two["modmults"],
                     "bytes": one["bytes"] + two["bytes"]}
    t, bound = work.least_time_s({"modmults": 100, "bytes": 10}, 10.0, 10.0)
    assert (t, bound) == (10.0, "modmul")
    t, bound = work.least_time_s({"modmults": 1, "bytes": 50}, 10.0, 10.0)
    assert (t, bound) == (5.0, "hbm")
