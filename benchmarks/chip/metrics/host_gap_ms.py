"""Mean device-idle gap between consecutive steps, in ms: from the last
device op of one ``bench.step`` span to the first of the next, on the
busiest device (session layer: ``TrainSession.step_once``'s host work
between steps: batch, dispatch, loss read-back)."""
import tracefile as tr


def read(ctx):
    plane = tr.busiest(ctx.trace)
    gaps = tr.step_gaps_ns(ctx.trace, plane)
    return sum(gaps) / len(gaps) / 1e6 if gaps else None
