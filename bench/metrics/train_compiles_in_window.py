"""Train-step programs traced inside the window: the increase of
``TrainRunner.train_compiles`` across it. A warm window has none."""


def compute(rec):
    return rec["compiles_in_window"]
