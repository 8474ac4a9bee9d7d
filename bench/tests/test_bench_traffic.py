"""The generator gives every seed the same work in another order."""
import collections

from bench import traffic as tf


def test_every_seed_gets_the_same_sizes():
    mix = tf.load_mix("reason")
    a, b = tf.Traffic(mix, 1, 1000), tf.Traffic(mix, 2**31 + 12345, 1000)
    assert collections.Counter(a.prompts) == collections.Counter(b.prompts)
    assert collections.Counter(a.outputs) == collections.Counter(b.outputs)
    assert a.prompts != b.prompts


def test_lengths_are_quantiles_within_bounds():
    mix = tf.load_mix("reason")
    xs = tf.quantile_lengths(mix["prompt_tokens"], 256)
    assert min(xs) >= 64 and max(xs) <= 256
    assert sorted(xs)[128] in range(126, 131)  # median near 128
    ys = tf.quantile_lengths(mix["output_tokens"], 256)
    assert min(ys) >= 1024 and max(ys) <= 4096


def test_first_wave_spreads_budgets_over_the_output_range():
    mix = tf.load_mix("reason")
    budgets = sorted(b for _, b in tf.Traffic(mix, 5, 1000).first_wave())
    w, top = mix["warm_decode_steps"], mix["output_tokens"]["max"]
    assert len(budgets) == mix["clients"]
    assert w < budgets[0] and budgets[-1] < top
    step = (top - w) / mix["clients"]
    assert all(abs((b - a) - step) <= 1 for a, b in zip(budgets, budgets[1:]))


def test_first_wave_has_the_same_sizes_for_every_seed():
    # set-up admits the same prompt lengths, so compiles the same splice
    # shapes, whatever the seed
    mix = tf.load_mix("reason")
    a = tf.Traffic(mix, 6, 1000).first_wave()
    b = tf.Traffic(mix, 2**33 + 7, 1000).first_wave()
    assert sorted(p for p, _ in a) == sorted(p for p, _ in b)
    assert sorted(o for _, o in a) == sorted(o for _, o in b)
    assert a != b


def test_first_wave_fits_the_context():
    mix = tf.load_mix("reason")
    assert all(p + o <= 4352 for p, o in tf.Traffic(mix, 5, 1000).first_wave())  # max_seq


def test_same_seed_same_tokens():
    mix = tf.load_mix("reason")
    assert (tf.Traffic(mix, 9, 1000).tokens(50) == tf.Traffic(mix, 9, 1000).tokens(50)).all()
