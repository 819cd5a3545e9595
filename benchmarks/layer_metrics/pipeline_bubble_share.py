"""1 - useful_lane_rotations / (2 x rotations) of the schedule the train
step compiled (``pipeline/model.py`` ``COMPILED_SCHEDULES``, written by the
executor as it traces; ``make_train_step`` returns the same two counters in
the step's metrics): the share of a lane's forward and backward slots that
compute on masked data, which no device trace can tell from work."""


def read(r):
    if r["kind"] != "training":
        return None
    from neuronx_distributed_llama3_2_tpu.pipeline import model as pipeline_model

    compiled = getattr(pipeline_model, "COMPILED_SCHEDULES", None)
    if not compiled:
        return None
    last = compiled[-1]
    return 100.0 * (1.0 - last["useful_lane_rotations"] / (2.0 * last["rotations"]))
