"""Mini-batch mode's shuffle in a job call (`shifu:train.shuffle`, opened by
`train/trainer.train_bags` beside the phases `program_spans.py` lists: the
training rows put in the job seed's order, padded and cut into batches, on
the host for host inputs and on the device for device inputs), mean
milliseconds a call. None where no call holds a `shifu:train.job`; 0 where
the job trains full batch."""

from benchmark import program_spans


def read(context):
    return program_spans.phase_ms(context["trace"], "shifu:train.shuffle")
