"""recall_at_10: mean recall@10 of every answer of the window against the
exact top 10 of the plain reference."""


def read(run):
    return run.recall
