"""(max - min) / mean of the frames each replica delivered in the window."""


def read(ctx):
    a, b = ctx["after"]["replica_frames"], ctx["before"]["replica_frames"]
    if not a or not b:
        return None
    per = [x - y for x, y in zip(a, b)]
    mean = sum(per) / len(per)
    return 100.0 * (max(per) - min(per)) / mean if mean > 0 else None
