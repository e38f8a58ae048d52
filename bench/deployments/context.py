"""One ``FlintContext``: the paper's deployment, a single driver whose
jobs run on the simulated Lambda pool, with one closed-loop client."""

from __future__ import annotations

LEDGER_FIELDS = ("lambda_gb_seconds", "lambda_requests", "sqs_requests",
                 "s3_gets", "s3_puts", "s3_lists", "s3_upload_parts")


class Context:
    def __init__(self, config: dict, table: str, data: bytes):
        from repro.core import FlintConfig, FlintContext

        self.ctx = FlintContext(config=FlintConfig(**config["engine"]))
        self.ctx.upload(table, data)
        self.clients = [self.ctx]

    @staticmethod
    def device_stats(client) -> dict:
        """The device counters of ``client``'s last job."""
        return dict(client.last_scheduler.device_stats)

    def counters(self) -> dict:
        """The CostLedger's running totals: request counts, GB-seconds
        and ``total_usd``."""
        led = self.ctx.ledger
        snap = {k: getattr(led, k) for k in LEDGER_FIELDS}
        snap["total_usd"] = led.total_usd
        return snap

    def close(self):
        self.clients = []


def open_deployment(config: dict, table: str, data: bytes) -> Context:
    return Context(config, table, data)
