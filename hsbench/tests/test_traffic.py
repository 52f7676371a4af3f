import numpy as np

from hsbench import datagen, traffic


def _values(table, column):
    return {"l_orderkey": np.arange(500), "l_partkey": np.arange(90), "o_orderkey": np.arange(300),
            "o_custkey": np.arange(70),
            "l_shipdate": np.datetime64("1993-01-01") + np.arange(400).astype("timedelta64[D]")}[column]


def _schedule(mix_name, seed, seconds=20.0):
    mix = traffic.load_mix(mix_name)
    templates = {t["name"]: traffic.Template(t["name"]) for t in mix["templates"]}
    drawers = {n: traffic.ParamDrawer(t, seed, mix.get("key_skew_zipf_s", 0.0), _values)
               for n, t in templates.items()}
    if mix["loop"] == "open":
        return [(r.due_s, r.text, r.tenant) for r in
                traffic.open_schedule(mix, templates, drawers, seed, seconds)]
    return [[(r.text, r.tenant) for r in seq[:40]] for seq in
            traffic.closed_sequences(mix, templates, drawers, 40)]


def test_open_schedule_is_a_pure_function_of_the_seed():
    a, b = _schedule("lookup-steady", 2400000123), _schedule("lookup-steady", 2400000123)
    assert a == b and len(a) > 50
    assert a != _schedule("lookup-steady", 2400000124)
    due = [t for t, _, _ in a]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    assert abs(len(a) / 20.0 - 5.0) < 2.0  # Poisson at 5/s


def _open(mix, seed, seconds=48.0):
    templates = {t["name"]: traffic.Template(t["name"]) for t in mix["templates"]}
    # the data under the keys is the same here, so that only the order can differ
    drawers = {n: traffic.ParamDrawer(t, 7, mix["key_skew_zipf_s"], _values) for n, t in templates.items()}
    return traffic.open_schedule(mix, templates, drawers, seed, seconds)


def test_an_open_loop_offers_every_seed_the_same_requests_in_another_order():
    mix = traffic.load_mix("lookup-steady")
    a, b = _open(mix, 1), _open(mix, 2**31 + 9)
    assert [r.due_s for r in a] == [r.due_s for r in b]          # the same arrivals
    assert sorted(r.text for r in a) == sorted(r.text for r in b)  # the same requests
    assert [r.text for r in a] != [r.text for r in b]            # dealt out in another order
    per_template = {}
    for r in a:
        per_template[r.template.name] = per_template.get(r.template.name, 0) + 1
    assert max(per_template.values()) - min(per_template.values()) <= 1  # equal shares, exactly


def test_exact_shares_hand_out_every_request():
    p = np.array([0.5, 0.3, 0.2])
    assert np.bincount(traffic._exact_shares(p, 10)).tolist() == [5, 3, 2]
    assert np.bincount(traffic._exact_shares(p, 7)).tolist() == [4, 2, 1]  # 3.5, 2.1, 1.4: the largest remainder first
    assert len(traffic._exact_shares(np.full(5, 0.2), 243)) == 243


def test_a_seed_beyond_32_signed_bits_is_taken():
    assert _schedule("lookup-steady", 2**31 + 77) == _schedule("lookup-steady", 2**31 + 77)


def test_closed_loop_asks_every_seed_for_the_same_queries_in_the_same_order():
    a, b = _schedule("analytic-closed", 1), _schedule("analytic-closed", 2)
    assert a == b  # the seed changes the data under the queries, nothing else
    assert a[0] != a[1]  # the two clients walk different orders
    assert len({t for seq in a for t, _ in seq}) == 14
    # each rotation of seven holds every template once
    assert len({t.split("from")[0] for t, _ in a[0][:7]}) == 7


def test_zipf_ranks_favour_the_head():
    r = traffic.zipf_ranks(np.random.default_rng(0), 1000, 1.1, 20000)
    assert r.min() >= 0 and r.max() < 1000
    assert (r == 0).mean() > 5 * (r == 99).mean()
    u = traffic.zipf_ranks(np.random.default_rng(0), 1000, 0.0, 20000)
    assert abs((u < 500).mean() - 0.5) < 0.02


def test_burst_arrivals_keep_silences():
    mix = {"burst": {"rate_per_s": 20.0, "on_s": 1.0, "off_s": 3.0}}
    t = traffic.arrival_times(mix, 5, 40.0)
    assert ((t % 4.0) < 1.0).all() and abs(len(t) / 40.0 - 5.0) < 1.5


def test_datagen_is_a_pure_function_of_the_seed():
    a = datagen.file_table("lineitem", 3, 16, 0.01, 2**31 + 5)
    assert a.equals(datagen.file_table("lineitem", 3, 16, 0.01, 2**31 + 5))
    assert not a.equals(datagen.file_table("lineitem", 3, 16, 0.01, 2**31 + 6))
    assert not a.equals(datagen.file_table("lineitem", 4, 16, 0.01, 2**31 + 5))
    assert datagen.rows_of("lineitem", 1.0) == 6_000_000 and datagen.rows_of("orders", 10.0) == 15_000_000
