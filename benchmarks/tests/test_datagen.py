import json
import os

import numpy as np

import datagen
from conftest import BENCH


def small():
    with open(os.path.join(BENCH, "configs", "als-ml25m-r64.json")) as f:
        return json.load(f)["rehearse"]["data"]


def test_graph_has_the_stated_counts():
    data = small()
    u, i = datagen.graph(data)
    assert len(u) == len(i) == data["n_edges"]
    assert u.dtype == i.dtype == np.int32
    pairs = u.astype(np.int64) * data["n_items"] + i
    assert len(np.unique(pairs)) == data["n_edges"]  # all pairs distinct
    du = np.bincount(u, minlength=data["n_users"])
    di = np.bincount(i, minlength=data["n_items"])
    assert du.min() >= data["user_degree"]["min_degree"]
    assert di.min() >= 1 and len(di) == data["n_items"]
    assert du.max() > 4 * np.median(du) and di.max() > 10 * np.median(di)


def test_graph_is_the_same_for_every_seed_and_values_follow_the_seed():
    data = small()
    u, i = datagen.graph(data)
    u2, i2 = datagen.graph(data)
    assert np.array_equal(u, u2) and np.array_equal(i, i2)
    big = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits
    r1, r1b = datagen.ratings(u, i, data, big), datagen.ratings(u, i, data, big)
    r2 = datagen.ratings(u, i, data, big + 1)
    assert np.array_equal(r1, r1b) and not np.array_equal(r1, r2)
    assert r1.dtype == np.float32
    assert set(np.unique(r1 * 2)) <= set(range(1, 11))  # half stars 0.5 .. 5.0
    assert r1.std() > 0.5  # not a constant: a model has something to fit
    ones = datagen.ratings(u, i, dict(data, ratings={"kind": "ones"}), 1)
    assert np.array_equal(ones, np.ones(len(u), np.float32))
