"""evaluator_ms.sweep: device busy time summed over the cell's chips, per
item of the traced window. On this path every device op is the dense
batched evaluator's (``evaluate_batch`` → ``egp_place_jax`` /
``agp_place_jax`` under ``shard_map``) or an input copy for it."""


def read(run):
    t = run.trace
    if t is None or not t.devices or not run.items:
        return None
    busy = sum(t.busy_ns(d) for d in t.devices)
    return busy / 1e6 / run.items
