"""The serving reference's two answers: a token routed the other way at
a near tie of the router is judged against the answer routed that way,
and only there."""
import numpy as np

from bench.refs import serve_plane as ref
from bench_tiny import SERVE, load_cell


def _config(tie):
    _, config, traffic = load_cell("phi35moe-skewed")
    return {**config, **SERVE, "router_tie": {"value": tie}}, traffic


def _answers(tie, seed=3):
    config, traffic = _config(tie)
    weights = ref.make_weights(config, seed, traffic["router_bias"])
    embed = ref.make_embedding(config, seed)
    toks = np.random.default_rng(seed).integers(0, 32, (4, 16))
    return np.asarray(ref.forward(weights, embed, toks, config))


def test_without_a_near_tie_both_answers_agree():
    out = _answers(tie=0.0)
    assert out.shape[0] == 2
    np.testing.assert_array_equal(out[0], out[1])


def test_a_token_routed_the_other_way_at_a_tie_has_no_gap():
    other = _answers(tie=1e9)[1]           # every boundary taken the other way
    assert np.all(ref.token_gaps(other, _answers(tie=1e9)) == 0.0)


def test_a_token_routed_the_other_way_beyond_a_tie_has_a_gap():
    other = _answers(tie=1e9)[1]
    gaps = ref.token_gaps(other, _answers(tie=0.0))
    assert gaps.max() > 0.0
