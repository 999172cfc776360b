"""update_edges_per_s (edges/s, host clock): directed edge updates
(inserts and deletes, both directions of each pair) applied inside the
window, over the window's length.  An operation whose publish was seen in
the window counts whole; the one in flight at the close counts for the
share of its time that lay inside the window, its time running from when
it was queued, or the publish before it was seen if that came later (the
service's writer applies one operation at a time), to its own publish."""


def read(rec):
    if not rec.writes:
        return None
    n, prev = 0.0, rec.t0
    for w in rec.writes:  # in the order queued, one publish each, in that order
        start = max(w.queued, prev)
        if w.done < rec.t1:
            n += w.directed
        elif start < rec.t1:
            n += w.directed * (rec.t1 - start) / (w.done - start)
        prev = w.done
    return n / (rec.t1 - rec.t0)
