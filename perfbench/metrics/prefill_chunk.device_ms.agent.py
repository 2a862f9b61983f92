"""prefill_chunk.device_ms.agent: Device time of one execution of the prefill
chunk program (32-token chunks and single-token tails together).
"""

from perfbench import readers


def read(run):
    return readers.program_ms(run, readers.PREFILL_CHUNK)
