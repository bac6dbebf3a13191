"""Tokens of all steps that end inside the window, over the window's
seconds and the cell's chips."""


def read(record: dict, args: dict):
    return record["tokens"] / record["window_s"] / record["chips"]
