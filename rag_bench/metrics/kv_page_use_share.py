"""Share of the KV pages reserved by decoding requests that hold K/V:
the `pages_live` over the `pages_reserved` attrs of the
`engine/decode_step` spans begun in the window, summed over steps."""


def read(obs):
    lo, hi = obs.window
    live = reserved = 0
    for r in obs.records:
        if (r.comp == "engine" and r.name == "decode_step" and r.ph == "B"
                and lo <= r.ts < hi and "pages_reserved" in r.attrs):
            live += r.attrs["pages_live"]
            reserved += r.attrs["pages_reserved"]
    if not reserved:
        return None
    return live / reserved * 100.0
