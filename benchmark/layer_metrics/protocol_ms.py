"""Protocol layer: median over statements of client latency minus the
time inside the runner call that served it (``runner.execute`` or the
``execute_batch`` it rode in).  Holds HTTP, JSON paging, the client's
poll sleep and, under concurrency, the wait for an executor thread."""

from statistics import median


def read(run):
    calls = sorted(run.runner_calls)
    used = set()
    outside = []
    for s in run.finished:
        for i, (t0, t1, sqls) in enumerate(calls):
            if i not in used and t0 >= s.t_issue and t1 <= s.t_done \
                    and s.instance.sql in sqls:
                if len(sqls) == 1:
                    used.add(i)
                outside.append((s.seconds - (t1 - t0)) * 1e3)
                break
    return median(outside) if outside else None
