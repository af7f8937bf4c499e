"""Share of the traced span in which no operation ran on the device."""


def read(obs):
    if obs.device.busy_s <= 0:
        return None
    return (1.0 - obs.device.busy_s / obs.extra["window_s"]) * 100.0
