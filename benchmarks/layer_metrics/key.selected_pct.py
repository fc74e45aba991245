"""(Query, key) pairs the sparse layers' selections hold, as a share of
the causal pairs: the program's counter ``selected_pairs`` (summed from the
selection itself) over rows x steps x layers x ``T (T + 1) / 2``, from the
configuration's sizes. ``sum_t min(t + 1, 2048) / (T (T + 1) / 2)`` is
23.44% at 16,384 events. ``None`` without the counter."""


def read(reading):
    counters = reading["stats"].get("counters") or {}
    selected = counters.get("selected_pairs")
    if selected is None:
        return None
    config = reading["config"]
    a = config["algorithm_params"]
    t = int(a["max_len"])
    causal = (int(a["batch_size"]) * int(a["steps"])
              * int(config["num_hidden_layers"]) * t * (t + 1) // 2)
    return 100.0 * selected / causal
