"""Model step: how uneven the load on this chip's experts is: per expert
layer the most-picked held expert's picks over the held experts' mean, pooled
over the layers. ``moe_busiest_expert_picks_total`` (the layers' largest
counts, summed) over ``moe_picks_held_total / moe_experts_held``, as the
engine has counted them up to the window's last scrape: the counts are
cumulative from the server's start, and a largest count's rise between two
scrapes is not the largest rise. 1 is an even load; a grouped product's time
follows its busiest group."""

BUSIEST = "quorum_tpu_engine_moe_busiest_expert_picks_total"
HELD = "quorum_tpu_engine_moe_picks_held_total"
EXPERTS = "quorum_tpu_engine_moe_experts_held"


def read(art):
    m = art["m1"]
    if not all(m.get(k) for k in (BUSIEST, HELD, EXPERTS)):
        return None
    return m[BUSIEST] * m[EXPERTS] / m[HELD]
