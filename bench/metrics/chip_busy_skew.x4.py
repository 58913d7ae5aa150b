"""The busiest chip's device time in the traced window over the mean of the
cell's chips: 1 when the broker spreads the work evenly."""


def read(rec):
    d = rec.device
    if d is None or len(d["busy_s"]) < 2 or sum(d["busy_s"]) <= 0:
        return None
    return max(d["busy_s"]) / (sum(d["busy_s"]) / len(d["busy_s"]))
