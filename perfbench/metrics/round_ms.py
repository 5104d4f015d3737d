"""Mean time of one FedAvg round: the window's length over the rounds it
completed (host clock; each dispatch ends in ``block_until_ready``), ms."""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx["rounds"]:
        return None
    return 1e3 * ctx["seconds"] / ctx["rounds"]
