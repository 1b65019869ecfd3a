"""Engine: the window pool's share of the live K/V bytes: (live blocks of
the window pool x a window block's bytes) / the same summed over both
pools, in percent, from the gauges xllm_engine_kv_blocks_live{pool=...}
and xllm_engine_kv_block_bytes{pool=...}, the mean of their readings at
the window's start and end. A sequence holds at most two window blocks
whatever its context, so the share falls as contexts grow; were nothing
freed behind a sequence it would be the layers' ratio, five sixths. A
program without the gauges gives nothing."""


def compute(w):
    shares = []
    for snap in (w.counters_start, w.counters_end):
        live = {}
        for pool in ("full", "window"):
            n = snap.get(f'xllm_engine_kv_blocks_live{{pool="{pool}"}}')
            size = snap.get(f'xllm_engine_kv_block_bytes{{pool="{pool}"}}')
            if n is None or size is None:
                return None
            live[pool] = n * size
        if sum(live.values()) > 0:
            shares.append(100.0 * live["window"] / sum(live.values()))
    return sum(shares) / len(shares) if shares else None
