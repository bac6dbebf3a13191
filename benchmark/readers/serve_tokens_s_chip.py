"""Generated tokens emitted inside the window (``eng.generated_tokens`` at
the close less its value at the opening) over the window's seconds and the
cell's chips."""


def read(record: dict, args: dict):
    return record["generated_tokens"] / record["window_s"] / record["chips"]
