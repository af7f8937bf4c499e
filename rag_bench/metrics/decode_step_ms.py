"""Mean `engine/decode_step` span: one paged decode over every active
slot plus the per-token sampling sync back to the host."""


def read(obs):
    spans = obs.spans("engine", "decode_step")
    if not spans:
        return None
    return sum(e - b for b, e, _ in spans) / len(spans) * 1e3
