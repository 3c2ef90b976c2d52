"""Traffic and weights: the same seed repeats, another seed differs."""
import numpy as np
import pytest

from bench import spec, traffic, weights
from bench.drivers import prefill, serve_closed

MIX = {"kind": "prefill", "batch": 2, "seq_len": 16, "tokens": "uniform"}
TINY = {"d_model": 32, "n_layers": 2, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 64, "vocab": 100, "qkv_bias": True, "tied_embeddings": True,
        "dtype": "bfloat16"}
SEEDS = [0, 7, 2**31 + 5, 2**33 + 7]


def tokens(seed, step):
    return np.asarray(prefill.tokens_fn(MIX, 100, seed)(step))


@pytest.mark.parametrize("seed", SEEDS)
def test_prefill_tokens_repeat_for_a_seed(seed):
    a, b = tokens(seed, 3), tokens(seed, 3)
    assert a.shape == (2, 16) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert a.min() >= 0 and a.max() < 100
    assert not np.array_equal(a, tokens(seed, 4))


def test_prefill_tokens_differ_across_seeds():
    draws = [tokens(s, 0) for s in SEEDS + [2**33 + 8]]
    for i in range(len(draws)):
        for j in range(i):
            assert not np.array_equal(draws[i], draws[j])


def test_high_bits_of_the_seed_count():
    assert not np.array_equal(tokens(2**33 + 7, 0), tokens(7, 0))


def test_weights_repeat_for_a_seed_and_differ_across_seeds():
    import jax

    layout = spec.architecture("decoder").layout(TINY)
    a = jax.tree.leaves(weights.generate(layout, 5, "bfloat16"))
    b = jax.tree.leaves(weights.generate(layout, 5, "bfloat16"))
    c = jax.tree.leaves(weights.generate(layout, 6, "bfloat16"))
    assert all(np.array_equal(np.asarray(x, np.float32),
                              np.asarray(y, np.float32)) for x, y in zip(a, b))
    assert not any(np.array_equal(np.asarray(x, np.float32),
                                  np.asarray(y, np.float32))
                   for x, y in zip(a, c))


def test_weight_layout_matches_the_program():
    from bench.spec import model_config
    from repro.models.lm import build_model
    import jax

    for moe in (None, {"n_experts": 4, "top_k": 2}):
        m = dict(TINY, name="tiny", family="moe" if moe else "dense",
                 moe=moe, tied_embeddings=moe is None)
        want = jax.tree.map(lambda s: tuple(s.shape),
                            build_model(model_config(m)).param_shapes())
        assert want == weights.shapes(spec.architecture("decoder").layout(m))


@pytest.mark.parametrize("bad", [{"batch": 2}, {"kind": "prefill"},
                                 {"kind": "prefill", "batch": 0,
                                  "seq_len": 4},
                                 dict(MIX, tokens="zipf")])
def test_validate_rejects_malformed_mixes(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)


CHAT = {"kind": "serve_closed", "clients": 3, "cycle": 8,
        "prompt_lens": [64, 128],
        "max_new": {"dist": "pareto", "alpha": 1.5, "min": 16, "max": 128},
        "server": {"n_slots": 8, "max_len": 256, "regions": 4}}


def test_cycle_sizes_follow_the_clipped_pareto():
    sizes = serve_closed.cycle_sizes(CHAT)
    assert [s for s, _ in sizes] == [64, 128] * 4
    news = [n for _, n in sizes]
    assert news == sorted(news) and news[0] >= 16 and news[-1] <= 128
    assert serve_closed.cycle_sizes(dict(CHAT, cycle=16))[-1][1] == 128  # clipped
    # the median of a Pareto law with x_m 16, alpha 1.5 is 16 * 2^(2/3)
    assert news[3] < 16 * 2 ** (2 / 3) < news[4]


def chat_requests(seed, clients=3, per_client=8):
    req = serve_closed.closed_requests(CHAT, 1000, seed)
    return [[req(c, j) for j in range(per_client)] for c in range(clients)]


@pytest.mark.parametrize("seed", SEEDS)
def test_chat_requests_repeat_for_a_seed(seed):
    a, b = chat_requests(seed), chat_requests(seed)
    for ra, rb in zip(sum(a, []), sum(b, [])):
        assert np.array_equal(ra[0], rb[0]) and ra[1] == rb[1]
        assert ra[0].dtype == np.int32 and 0 <= ra[0].min() <= ra[0].max() < 1000


def sizes_of(reqs):
    return [[(len(p), n) for p, n in client] for client in reqs]


def test_every_seed_offers_the_same_sizes_in_another_order():
    """Every seed gives the callers the same streams of sizes, rotated
    among them; each round (the j-th request of every caller) spans the
    law, and the rounds go through the whole cycle."""
    base = sizes_of(chat_requests(SEEDS[0]))
    rotations = set()
    for seed in SEEDS + [1, 2, 3]:
        got = sizes_of(chat_requests(seed))
        r = next(r for r in range(3) if got == base[r:] + base[:r])
        rotations.add(r)
    assert len(rotations) > 1
    seen = {s for client in base for s in client}
    assert seen == set(serve_closed.cycle_sizes(CHAT))
    first_round = sorted(news for _, news in (c[0] for c in base))
    assert first_round[0] < 30 < first_round[-1]
    a, b = chat_requests(SEEDS[0]), chat_requests(SEEDS[1])
    assert not np.array_equal(a[0][0][0][:16], b[0][0][0][:16])


@pytest.mark.parametrize("bad", [dict(CHAT, clients=0),
                                 dict(CHAT, prompt_lens=[]),
                                 dict(CHAT, max_new={"dist": "zipf"}),
                                 dict(CHAT, server={"n_slots": 8})])
def test_validate_rejects_malformed_serve_mixes(bad):
    with pytest.raises(ValueError):
        traffic.validate(bad)


def test_the_shipped_mixes_validate():
    traffic.validate(MIX)
    traffic.validate(CHAT)
    for path in traffic.TRAFFIC_DIR.glob("*.json"):
        traffic.load(path.stem)


def test_a_mix_is_driven_by_the_driver_its_kind_names(monkeypatch):
    """A new kind of traffic is a new driver file: validation goes to the
    module ``bench.drivers.<kind>``, and a kind with no driver is refused."""
    import sys
    import types

    seen = []
    fake = types.ModuleType("bench.drivers.fake_kind")
    fake.validate = seen.append
    monkeypatch.setitem(sys.modules, "bench.drivers.fake_kind", fake)
    traffic.validate({"kind": "fake_kind", "rate": 3})
    assert seen == [{"kind": "fake_kind", "rate": 3}]
    assert traffic.driver("prefill") is prefill
    with pytest.raises(ValueError, match="no driver"):
        traffic.validate({"kind": "no_such_kind"})


def test_token_distributions_are_found_by_name(monkeypatch, tmp_path):
    """A new token distribution is a new file ``bench/tokens/<dist>.py``
    whose parameters come from the mix."""
    (tmp_path / "ends.py").write_text(
        "def device(key, shape, vocab, params):\n"
        "    import jax.numpy as jnp\n"
        "    return jnp.full(shape, params['id'], jnp.int32)\n"
        "def host(rng, n, vocab, params):\n"
        "    import numpy as np\n"
        "    return np.full(n, params['id'], np.int32)\n")
    monkeypatch.setattr(traffic, "TOKENS_DIR", tmp_path)
    mix = dict(MIX, tokens={"dist": "ends", "id": 7})
    assert (np.asarray(prefill.tokens_fn(mix, 100, 3)(0)) == 7).all()
    req = serve_closed.closed_requests(dict(CHAT, tokens=mix["tokens"]),
                                       100, 3)
    assert (req(0, 0)[0] == 7).all()
    with pytest.raises(ValueError, match="no token distribution"):
        traffic.tokens(dict(MIX, tokens="no_such_dist"))


@pytest.mark.parametrize("seed", [3, 2**33 + 7])
def test_zipf_tokens_are_skewed_the_same_way_for_every_seed(seed):
    """The skewed distribution a later mix can name as data: the hottest
    id takes about 1 / H(vocab) of the draws on the device and on the
    host, and it is the same id for every seed."""
    mix = dict(MIX, batch=8, seq_len=512, tokens={"dist": "zipf", "a": 1.1})
    dev = np.asarray(prefill.tokens_fn(mix, 1000, seed)(0)).ravel()
    base = np.asarray(prefill.tokens_fn(mix, 1000, 5)(0)).ravel()
    host = serve_closed.closed_requests(
        dict(CHAT, tokens=mix["tokens"], prompt_lens=[4096]), 1000, seed)
    hot_share = 1 / np.sum(np.arange(1, 1001) ** -1.1)
    for ids, other in ((dev, base), (host(0, 0)[0], host(1, 0)[0])):
        assert ids.min() >= 0 and ids.max() < 1000 and ids.dtype == np.int32
        top = np.bincount(ids, minlength=1000).argmax()
        assert top == np.bincount(other, minlength=1000).argmax()
        assert abs(np.mean(ids == top) - hot_share) < 0.04
    with pytest.raises(ValueError):
        traffic.tokens(dict(MIX, tokens={"dist": "zipf", "a": -1}))
