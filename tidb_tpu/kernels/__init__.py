"""The device-lowering layer under the three engines (cop engine
`copr/`, MPP engine `parallel/`, window executor `executor/`).

    lowering    SQL expressions onto device lanes: the rewrite into dict-
                code space, evaluation over lanes, the selection mask,
                sorted string dictionaries
    primitives  the kernels themselves: segmented reduces, the partial
                aggregates, the lexicographic sort, the top-k family,
                clustered run totals
    booking     a device call as the timeline sees it: compile/dispatch,
                upload and fetch, each through `utils/timeline.boundary`

Every arrow points down: this package imports `jaxenv`, `expr`,
`mysqltypes`, `chunk` and `utils` only, never an engine, the planner,
the session or the scheduler (`tests/test_analyze.py` holds it to that).
Lane codecs are not here: `copr/tilecache.py` owns the format and
`TPUEngine._decode_lane` its in-program decode.
"""
