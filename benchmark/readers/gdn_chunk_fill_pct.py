"""How full the chunked prompt passes of the linear layers are: the
64-position chunks that hold a live position over the chunks the padded
passes scan (``prefill_batch x W / 64`` a pass), summed over the passes begun
in the window.  The engine samples both at each pass, from the rows' lengths
alone (``serve.gdn.chunks_live``, ``serve.gdn.chunks_scanned``); ``None``
where the program has no such rings."""

from benchmark import ring


def read(record: dict, args: dict):
    live = ring.total(record, "serve.gdn.chunks_live")
    scanned = ring.total(record, "serve.gdn.chunks_scanned")
    if live is None or not scanned:
        return None
    return 100.0 * live / scanned
