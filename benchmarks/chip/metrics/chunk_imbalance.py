"""How unevenly the scheduler spread the grid's chunks over the chips: the
most chunks whose device copy one chip holds after a solve over the mean
(chunks over chips), averaged over the window's solves. 1 is even; the
number of chips is every chunk on one."""


def read(ctx):
    solves = [[len(names) for names in placed]
              for placed in ctx["notes"].get("placement", [])]
    if not solves or not all(sum(n) for n in solves):
        return None
    return sum(max(n) * len(n) / sum(n) for n in solves) / len(solves)
