"""Coalescer layer: search commands a stacked KNN dispatch carried, over the
slice (``members`` of every ``kernel`` span whose ``verb`` is ``FT.SEARCH``):
what ``coalesce.cmds_per_kernel`` says of the sketch runs, said of a frame's
run of searches.  The frame's length where the whole run rides one dispatch;
1 would be a bank read a search.  None where no such span is: searches that
went alone, a program that stacks none."""


def read(obs):
    members = [int(s["attrs"].get("members", 1)) for f in obs.frames
               for s in f["spans"]
               if s["name"] == "kernel" and s["attrs"].get("verb") == "FT.SEARCH"]
    if not members:
        return None
    return sum(members) / len(members)
