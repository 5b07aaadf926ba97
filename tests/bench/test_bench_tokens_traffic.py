"""The token generator: deterministic per seed, and following its
stated distribution."""
import numpy as np

from bench import tokens


def test_token_rows_are_a_function_of_seed_batch_and_row():
    a = tokens.rows(2**31 + 7, 3, 4, 256, 1000)
    assert a.dtype == np.int32 and a.shape == (4, 256)
    assert (a == tokens.rows(2**31 + 7, 3, 4, 256, 1000)).all()
    assert not (a == tokens.rows(2**31 + 8, 3, 4, 256, 1000)).all()
    assert not (a == tokens.rows(2**31 + 7, 4, 4, 256, 1000)).all()
    assert len({r.tobytes() for r in a}) == 4          # every row differs
    assert a.min() >= 0 and a.max() < 1000


def test_token_rows_are_zipf_distributed():
    a = tokens.rows(1, 0, 8, 4096, 49152, a=1.3)
    counts = np.bincount(a.ravel(), minlength=49152)
    # Zipf(1.3): id 1 is the most frequent; P(1) = 1 / zeta(1.3) ~ 0.27
    assert counts.argmax() == 1
    assert 0.22 < counts[1] / a.size < 0.32
    assert counts[1] > counts[2] > counts[4]


def test_lm_batch_labels_are_the_next_tokens():
    b = tokens.lm_batch(5, 0, 2, 128, 512)
    assert (b["tokens"][:, 1:] == b["labels"][:, :-1]).all()
