"""Server scheduler: time a query waited for admission and for a worker
(``queueMs`` of the Admission and SchedulerQueue spans), median."""

from benchmarks.lib.stats import find, median, ms, roots


def read(ctx):
    return median([ms(find(root, "Admission") + find(root, "SchedulerQueue"),
                      "queueMs")
                   for _, root in roots(ctx["records"])])
