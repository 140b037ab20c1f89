"""Trained tokens per second per chip over the whole window: whole steps,
each ending in a host read of the loss."""


def read(facts):
    return facts.get("train_tok_s_chip")
