"""Op kernels (ops/moe.py): the fullest held expert's tokens over the mean
of the held experts', from the step's counters (fetched with the loss; the
mean over the window's fetches).  Read as
``expert_load_max_over_mean.train``."""


def read(facts):
    return facts.get("expert_load_max_over_mean")
