import als_cost


def test_against_hand_worked_numbers():
    # 10 edges, 3 users, 2 items, rank 2, 1 iteration:
    # normal equations 2 sides x 10 edges x (2*4 + 2*2) = 240 flop
    # solves 5 entities x (8/3 + 8) = 53.33 flop
    # bytes: gathers 2 x 10 x 2 x 2 B = 80; A write+read 2 x 5 x 4 x 4 B = 160
    c = als_cost.als_cost(10, 3, 2, 2, 1)
    assert abs(c["flops"] - (240 + 5 * (8 / 3 + 8))) < 1e-9
    assert c["bytes"] == 240
    c3 = als_cost.als_cost(10, 3, 2, 2, 3)
    assert abs(c3["flops"] - 3 * c["flops"]) < 1e-9 and c3["bytes"] == 720


def test_ml25m_rank64_is_memory_bound_at_about_17_ms_an_iteration():
    c = als_cost.als_cost(25_000_095, 162_541, 59_047, 64, 1)
    least = als_cost.least_seconds(c, {"flops_per_s": 197e12, "bytes_per_s": 819e9})
    assert least["bound"] == "memory"
    assert 0.42e12 < c["flops"] < 0.45e12 and 13e9 < c["bytes"] < 14.5e9
    assert 0.016 < least["seconds"] < 0.018
    fast = als_cost.least_seconds(c, {"flops_per_s": 1e12, "bytes_per_s": 819e9})
    assert fast["bound"] == "compute"
