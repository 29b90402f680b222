"""Finalize + encode, the wire: socket writes the server's packet writer
made (`tidb_tpu_wire_write_calls_total`, one per `sendall`) per statement
completed. A response that leaves whole reads 1; one sent packet by
packet reads its packet count, and each small write after the first can
wait for the client's delayed ACK. The counter counts every connection's
writes, the probe's two snapshots among them."""

from benchlib import spans


def read(ctx):
    return spans.per_stmt(
        ctx, spans.counter_delta(ctx, "tidb_tpu_wire_write_calls_total"))
