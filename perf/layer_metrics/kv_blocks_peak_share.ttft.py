"""Largest share of the KV pool that admitted requests held at one time,
%: whole blocks from the requests' own ``admitted`` / ``first_token`` /
``retired`` stamps over the pool the gauges report (used + free).  The
``kv_blocks_used`` gauge alone would not do: it counts the prefix cache's
evictable blocks too and sits at 100 % once the pool has filled."""


def read(obs):
    if not obs["blocks"]:
        return None
    used, free = obs["blocks"][-1]
    size = obs["cell"]["traffic"]["system"]["engine"]["kv_block_size"]
    return 100.0 * obs["held_kv_tokens_peak"] / ((used + free) * size)
