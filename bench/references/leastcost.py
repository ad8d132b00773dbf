"""A request's first placement costs what LeastCostMap gives on the
residual it was made on, over the whole network; a request fits nowhere
where LeastCostMap finds no placement (``inf``)."""


def first_cost(ref, r, *, max_supersteps=None) -> float:
    return ref.least_cost(r.creq, r.breq, r.src, r.dst,
                          max_supersteps=max_supersteps)
