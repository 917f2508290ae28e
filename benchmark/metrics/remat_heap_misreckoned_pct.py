"""How far the engine's arithmetic for a grad program's heap (the remat
budget's `reckoned_heap_bytes`) is from the compiler's `temp_bytes`, at
the packed grid where it is furthest."""

from benchmark import program_memory


def read(records):
    return program_memory.heap_misreckoned_pct(records)
