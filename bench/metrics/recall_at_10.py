"""recall_at_10: mean recall@10 of the queries answered, against the exact
brute force (an exploration query's own vertex left out)."""


def read(run):
    return run.recall
