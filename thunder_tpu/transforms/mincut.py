"""Min-cut solver for rematerialization: native C++ Dinic with Python fallback.

Reference parity: thunder/core/rematerialization.py:245 (igraph max-flow).
The native module (csrc/mincut.cpp) compiles lazily on first use with g++
into the checkout's ignored cache directory (``.jax_cache/native``: built from
what git commits, on the machine that runs it); the pure-Python Dinic below is
the fallback when no toolchain is available. Which of the two is in use is
logged once on the ``thunder_tpu`` logger. Both implement the same interface:

    min_cut(n_nodes, edges=[(u, v, cap)], s, t) -> (flow, source_side_set)

Capacities ≥ INF_CAP are treated as uncuttable.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
from collections import deque
from typing import Sequence

logger = logging.getLogger("thunder_tpu")

INF_CAP = 1 << 60

_lib = None
_lib_tried = False


def _load_native():
    global _lib, _lib_tried
    if _lib_tried:
        return _lib
    _lib_tried = True
    from thunder_tpu.resilience.compile_cache import default_cache_dir

    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "csrc", "mincut.cpp")
    cache_dir = os.path.join(default_cache_dir(), "native")
    so_path = os.path.join(cache_dir, "libttmincut.so")
    try:
        os.makedirs(cache_dir, exist_ok=True)
        if not os.path.exists(so_path) or os.path.getmtime(so_path) < os.path.getmtime(src):
            # Build to a private name, then rename: a second process never
            # loads a half-written library.
            tmp_path = f"{so_path}.{os.getpid()}.tmp"
            subprocess.run(
                ["g++", "-O2", "-shared", "-fPIC", src, "-o", tmp_path],
                check=True, capture_output=True, timeout=120,
            )
            os.replace(tmp_path, so_path)
        lib = ctypes.CDLL(so_path)
        lib.tt_mincut.restype = ctypes.c_int64
        lib.tt_mincut.argtypes = [
            ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_int32, ctypes.c_int32, ctypes.POINTER(ctypes.c_uint8),
        ]
        _lib = lib
        logger.info("min-cut solver: native (%s)", so_path)
    except (OSError, subprocess.SubprocessError) as e:
        # No toolchain, a failed build or an unloadable library: the Python
        # Dinic computes the same cut, slower. Said once, with the cause.
        _lib = None
        logger.warning("min-cut solver: pure Python (native build unavailable: %s: %s)",
                       type(e).__name__, e)
    return _lib


def _min_cut_py(n: int, edges: Sequence[tuple], s: int, t: int):
    """Pure-Python Dinic (fallback)."""
    graph: list[list[list]] = [[] for _ in range(n)]  # [to, cap, rev_idx]

    def add(u, v, cap):
        graph[u].append([v, cap, len(graph[v])])
        graph[v].append([u, 0, len(graph[u]) - 1])

    for u, v, c in edges:
        add(u, v, c)

    flow = 0
    while True:
        level = [-1] * n
        level[s] = 0
        q = deque([s])
        while q:
            u = q.popleft()
            for e in graph[u]:
                if e[1] > 0 and level[e[0]] < 0:
                    level[e[0]] = level[u] + 1
                    q.append(e[0])
        if level[t] < 0:
            break
        it = [0] * n

        def dfs(u, f):
            if u == t:
                return f
            while it[u] < len(graph[u]):
                e = graph[u][it[u]]
                v = e[0]
                if e[1] > 0 and level[u] < level[v]:
                    d = dfs(v, min(f, e[1]))
                    if d > 0:
                        e[1] -= d
                        graph[v][e[2]][1] += d
                        return d
                it[u] += 1
            return 0

        while True:
            f = dfs(s, INF_CAP)
            if f == 0:
                break
            flow += f

    side = set()
    q = deque([s])
    side.add(s)
    while q:
        u = q.popleft()
        for e in graph[u]:
            if e[1] > 0 and e[0] not in side:
                side.add(e[0])
                q.append(e[0])
    return flow, side


def min_cut(n: int, edges: Sequence[tuple], s: int, t: int):
    """(max_flow, source_side_node_set). Uses the C++ solver when available."""
    lib = _load_native()
    if lib is None:
        return _min_cut_py(n, edges, s, t)
    m = len(edges)
    eu = (ctypes.c_int32 * m)(*[e[0] for e in edges])
    ev = (ctypes.c_int32 * m)(*[e[1] for e in edges])
    ec = (ctypes.c_int64 * m)(*[min(int(e[2]), INF_CAP) for e in edges])
    side = (ctypes.c_uint8 * n)()
    flow = lib.tt_mincut(n, m, eu, ev, ec, s, t, side)
    return int(flow), {i for i in range(n) if side[i]}


def using_native() -> bool:
    return _load_native() is not None
