"""The plain reference's side of a temporal-property check: the behaviour
graph of a binding level by level, and the verdict of ``<>P`` on it.

Built on ``benchmark/ref/pyeval.py``'s ``initial_states``, ``successors``
and ``termination_goal`` alone (it imports nothing of the program).  What
that module's ``check_eventually`` lacks is here: a breadth-first search
that also says, for each level, how many ``<Next>_vars`` edges leave it
(one for every enabled successor that differs from its state: a step
that leaves the state as it is is a stutter and no ``<Next>_vars``
step), how many of its states satisfy ``P`` and how many are dead ends
(not-``P`` states with no state-changing successor), and the verdict of
the whole graph under ``WF_vars(Next)`` or under no fairness, with a
lasso where it is violated.

A state's number is its place in discovery order, so the states of a
level are a range of numbers and the edges come out sorted by source.
"""

from __future__ import annotations

from array import array

from benchmark.ref import pyeval as pe

SATISFIED_WF = "all fair behaviors reach the goal"


def search(c, max_levels=None, keep_graph=False, goal=pe.termination_goal):
    """Breadth-first search of the binding ``c``.

    ``levels``: one ``{"size", "edges", "goal", "dead_ends"}`` per level
    whose states were all expanded (with ``max_levels`` the first that
    many; ``complete`` says whether that was the whole graph, and
    ``next_size`` is the size of the first level not expanded, 0 at the
    end).  With ``keep_graph`` the result also holds ``n_init``,
    ``is_goal`` (a bytearray over the states), ``starts`` and ``dst``
    (the edges as rows of a CSR matrix), for ``verdict``.
    """
    seen = {}
    frontier = []
    for s in pe.initial_states(c):
        if s not in seen:
            seen[s] = len(seen)
            frontier.append(s)
    n_init = len(frontier)
    levels = []
    is_goal, starts, dst = bytearray(), array("q", [0]), array("q")
    while frontier and (max_levels is None or len(levels) < max_levels):
        new = []
        edges = goals = dead = 0
        for s in frontier:
            me, out = seen[s], 0
            for _a, t in pe.successors(c, s):
                j = seen.get(t)
                if j is None:
                    j = seen[t] = len(seen)
                    new.append(t)
                if j != me:
                    out += 1
                    if keep_graph:
                        dst.append(j)
            g = bool(goal(c, s))
            goals += g
            dead += (not g) and out == 0
            edges += out
            if keep_graph:
                is_goal.append(g)
                starts.append(len(dst))
        levels.append({"size": len(frontier), "edges": edges, "goal": goals,
                       "dead_ends": dead})
        frontier = new
    out = {"levels": levels, "complete": not frontier,
           "next_size": len(frontier)}
    if keep_graph:
        if frontier:
            raise ValueError("a graph is kept of a whole search only")
        out.update(n_init=n_init, is_goal=is_goal, starts=starts, dst=dst)
    return out


def verdict(graph, fairness):
    """``(holds, reason, lasso)`` of ``<>P`` on a graph kept by
    ``search``.  Without fairness a behaviour may stutter for ever at an
    initial state, so ``<>P`` holds only if every initial state satisfies
    ``P``.  Under ``WF_vars(Next)`` it is violated exactly if, walking
    only not-``P`` states from a not-``P`` initial state, a dead end or a
    cycle of state-changing steps is reached.  ``lasso`` is ``(prefix,
    cycle)`` as state numbers, None where the property holds."""
    n_init, is_goal = graph["n_init"], graph["is_goal"]
    starts, dst = graph["starts"], graph["dst"]
    bad = [i for i in range(n_init) if not is_goal[i]]
    if fairness == "none":
        if bad:
            return False, "an initial state may stutter for ever", \
                ([bad[0]], [bad[0]])
        return True, "every initial state satisfies the goal", None
    if fairness != "wf_next":
        raise ValueError(f"unknown fairness: {fairness}")
    # depth-first search of the not-P states: 0 new, 1 on the path, 2 done
    colour = bytearray(len(is_goal))
    for root in bad:
        if colour[root]:
            continue
        colour[root] = 1
        path, at = [root], [starts[root]]
        while path:
            u = path[-1]
            if starts[u + 1] == starts[u]:
                return False, "a not-goal state has no state-changing " \
                    "successor", (list(path), [u])
            if at[-1] == starts[u + 1]:
                colour[u] = 2
                path.pop()
                at.pop()
                continue
            v = dst[at[-1]]
            at[-1] += 1
            if is_goal[v] or colour[v] == 2:
                continue
            if colour[v] == 1:
                k = path.index(v)
                return False, "a cycle of not-goal states is fairly " \
                    "traversable", (path[:k + 1], path[k:])
            colour[v] = 1
            path.append(v)
            at.append(starts[v])
    return True, SATISFIED_WF, None


def profile_of(levels):
    """The four per-level columns of ``search``'s levels, as lists."""
    return {k: [lv[k] for lv in levels]
            for k in ("size", "edges", "goal", "dead_ends")}
