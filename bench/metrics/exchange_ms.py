"""Shard exchange: device time per PCG iteration in which only collectives
run -- the exchange left exposed by the other work."""


def read(m):
    if m.chips < 2:
        return None
    return m.per_iteration_ms("exchange")
