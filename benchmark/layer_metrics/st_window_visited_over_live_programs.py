"""Kernels layer: grid programs the window layers' three flash kernels VISIT
over those that are LIVE (have an unmasked score), a head, from the arm the
program states in ``kernel_routes`` (``fwd 1024x1024 70/80, ...``). 1.0 =
only live blocks are visited; the band's grid visits the most live blocks
any one outer block has (5 of a row's 16 at W 4096 and 1024-tiles), so the
first blocks of a sequence leave a remainder (80 / 70); the causal grid
would read 256 / 70."""

import smallthinker_trace


def reduce(run: dict):
    return smallthinker_trace.window_visited_over_live(run)
