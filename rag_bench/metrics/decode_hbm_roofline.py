"""Bytes a decode step must move (bf16 weights once, the routed experts,
K/V at each active row's live length; counts.decode_step), over the
device time of the decode program, as a share of HBM bandwidth."""


def read(obs):
    t = obs.device_time("decode")
    w = obs.work.get("decode")
    if not t or not w or not w.bytes:
        return None
    return w.bytes / t / obs.peaks["hbm_bytes_per_s"] * 100.0
