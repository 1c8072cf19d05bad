"""Host seconds per step in the trainer's draw of the next sharded batch:
the mean length of the ``train.data`` spans in the window
(``span_reduce.per_step``)."""


def read(rec):
    return rec.get("spans", {}).get("per_step", {}).get("trainer.data_s")
